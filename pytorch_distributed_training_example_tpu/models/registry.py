"""Model registry: name -> (module, task kind, input template, FLOPs, TP rules).

The torchvision-factory equivalent (reference builds models via
``torchvision.models.resnet50()`` etc., SURVEY.md §2a #4) plus the metadata
the framework needs: which task head to use, an input template for sharded
init, a forward-FLOPs estimate for MFU accounting, and per-family tensor-
parallel rule tables.

Families: ResNet (torchvision depths), ViT, GPT-2, Llama and
the Granite 4.0-H hybrid (Mamba-2 state-space mixers among GQA attention:
``granite4_h_micro`` at its published sizes, ``granite4_h_micro_share`` one
chip's share of it, ``granite_hybrid_tiny`` for tests; training only,
``dp``/``fsdp`` only) and the ``afmoe`` family (gated window and full
attention over a sigmoid-routed mixture with a shared expert:
``trinity_mini`` at its published sizes, ``trinity_mini_share`` one chip's
share of it, ``afmoe_tiny`` for tests; training only, ``dp``/``fsdp`` only)
and the ``smallthinker`` family (every layer a mixture of ReLU-gated experts
routed ahead of attention by a softmax over the chosen, under GQA that
alternates a position-free full layer with window layers:
``smallthinker_21b`` at its published sizes, ``smallthinker_21b_share`` one
chip's share of it, ``smallthinker_tiny`` for tests; training only,
``dp``/``fsdp`` only) and the ``glm_moe_lite`` family (latent attention over
a sigmoid-routed mixture with a shared expert, and a multi-token-prediction
module whose loss rides the ``losses`` collection: ``glm47_flash`` at its
published sizes, ``glm47_flash_share`` one chip's share of it,
``glm_moe_lite_tiny`` for tests; training only, ``dp``/``fsdp`` only) and
the ``nemotron_h`` family (every layer one mixer alone by a published pattern
string: a Mamba-2 mixer with grouped B/C, an ungated squared-ReLU expert layer
beside a shared expert, or position-free GQA: ``nemotron3_nano`` at its
published sizes, ``nemotron3_nano_share`` one chip's share of it,
``nemotron_h_tiny`` for tests; training only, ``dp``/``fsdp`` only) and the
``lfm2_moe`` family (a doubly gated three-tap convolution in three layers of
four beside q/k-normed rotary GQA, over a sigmoid-and-bias routed mixture
with no shared expert and a tied embedding: ``lfm2_8b_a1b`` at its published
sizes, ``lfm2_8b_a1b_share`` one chip's share of it, ``lfm2_moe_tiny`` for
tests; training only, ``dp``/``fsdp`` only) and the ``qwen3_next`` family (a
gated delta rule in three layers of four beside gated GQA with a quarter of
the head rotary, every layer over a softmax-routed mixture of 512 experts
with a sigmoid-gated shared expert: ``qwen3_next_80b`` at its published
sizes, ``qwen3_next_80b_share`` one chip's share of it, ``qwen3_next_tiny``
for tests; training only, ``dp``/``fsdp`` only) and the ``xing4`` family
(``model_type`` ``xing4_0``: the ``glm_moe_lite`` family's latent attention,
with a value width under its query/key width and YaRN positions, and its
expert layer, on a residual path four streams wide that
manifold-constrained hyper-connections read, mix and write: ``xing4_29b`` at
its published sizes without the prediction layer, which the family does not
build, ``xing4_29b_share`` one chip's share of it, ``xing4_tiny`` for tests;
training only, ``dp``/``fsdp`` only).
"""

from __future__ import annotations

import dataclasses
import inspect
from typing import Any, Callable

import jax.numpy as jnp


@dataclasses.dataclass
class ModelBundle:
    module: Any                      # flax module (constructed, not initialized)
    task: str                        # "classification" | "lm"
    input_template: tuple            # abstract sample inputs for init
    fwd_flops_per_example: float     # forward FLOPs for one example (MFU accounting)
    rules: dict[str, tuple]          # strategy name -> partition-rule table
    examples_unit: str = "images"    # "images" | "sequences" (throughput label)


_REGISTRY: dict[str, Callable[..., ModelBundle]] = {}


def register(name):
    def deco(fn):
        _REGISTRY[name] = fn
        return fn
    return deco


def list_models() -> list[str]:
    return sorted(_REGISTRY)


#: What a caller may say about a model beyond its sizes and dtypes, with the
#: value that asks for nothing. A family's builder names the ones it takes
#: and lets the rest pass (the Trainer hands every family the whole set),
#: except the two in ``_NEVER_DROPPED``: those are refused, at any other
#: value, by a family that has no such knob.
_OPTIONS = dict(remat=False, remat_policy="nothing", sp=False,
                attn_impl="auto", dropout=0.0)
_NEVER_DROPPED = {
    "dropout": "the Llama and ResNet families have no dropout knob, matching "
               "the reference factories",
    "remat_policy": "only the Llama family exposes checkpoint-policy tuning",
}


def create_model(name: str, *, num_classes: int = 1000, image_size: int = 224,
                 seq_len: int = 1024, dtype=jnp.bfloat16,
                 param_dtype=jnp.float32, logits_dtype=jnp.float32,
                 **options) -> ModelBundle:
    """``options`` (``_OPTIONS``) go whole to the family's builder, which
    names the ones it takes."""
    if name not in _REGISTRY:
        raise ValueError(f"unknown model {name!r}; have {list_models()}")
    builder = _REGISTRY[name]
    named = inspect.signature(builder).parameters
    for opt, value in options.items():
        if opt not in _OPTIONS:
            raise TypeError(
                f"create_model() got an unexpected option {opt!r}; have "
                f"{sorted(_OPTIONS)}")
        if (opt in _NEVER_DROPPED and opt not in named
                and value != _OPTIONS[opt]):
            raise ValueError(
                f"model {name!r} does not implement {opt}; {opt}={value!r} "
                f"would be silently ignored ({_NEVER_DROPPED[opt]})")
    return builder(num_classes=num_classes, image_size=image_size,
                   seq_len=seq_len, dtype=dtype, param_dtype=param_dtype,
                   logits_dtype=logits_dtype, **{**_OPTIONS, **options})


@register("vit_b16")
def _vit_b16(*, num_classes, image_size, dtype, param_dtype, remat,
             attn_impl="auto", dropout=0.0, **_):
    from pytorch_distributed_training_example_tpu.models import vit

    # dropout defaults to 0.0 for parity with the reference model factory
    # (torchvision vit_b_16: dropout=0.0, attention_dropout=0.0).
    module = vit.vit_b16(num_classes=num_classes, dtype=dtype,
                         param_dtype=param_dtype, remat=remat, dropout=dropout,
                         attn_impl=attn_impl)
    return ModelBundle(
        module=module, task="classification",
        input_template=(jnp.zeros((2, image_size, image_size, 3), jnp.float32),),
        fwd_flops_per_example=vit.flops_per_image(image_size),
        rules={"fsdp_tp": vit.TP_RULES, "tp": vit.TP_RULES},
    )


@register("vit_tiny")
def _vit_tiny(*, num_classes, image_size, dtype, param_dtype, remat,
              attn_impl="auto", dropout=0.0, **_):
    from pytorch_distributed_training_example_tpu.models import vit

    module = vit.vit_tiny(num_classes=num_classes, dtype=dtype,
                          param_dtype=param_dtype, remat=remat,
                          dropout=dropout, attn_impl=attn_impl)
    return ModelBundle(
        module=module, task="classification",
        input_template=(jnp.zeros((2, image_size, image_size, 3), jnp.float32),),
        fwd_flops_per_example=vit.flops_per_image(image_size, 4, 2, 64, 128),
        rules={"fsdp_tp": vit.TP_RULES, "tp": vit.TP_RULES},
    )


def _lm_bundle(module, tp_rules, seq_len, n_params_fn):
    from pytorch_distributed_training_example_tpu.utils import metrics as metrics_lib

    flops_tok = metrics_lib.transformer_flops_per_token(
        n_params_fn(module), seq_len, module.num_layers, module.d_model)
    return ModelBundle(
        module=module, task="lm",
        input_template=(jnp.zeros((2, seq_len), jnp.int32),),
        fwd_flops_per_example=flops_tok * seq_len,
        rules={"fsdp_tp": tp_rules, "tp": tp_rules},
        examples_unit="sequences",
    )


@register("gpt2")
def _gpt2(*, seq_len, dtype, param_dtype, remat, sp=False, attn_impl="auto",
          dropout=0.0, logits_dtype, **_):
    from pytorch_distributed_training_example_tpu.models import gpt2

    # GPT-2 carries the reference family's dropout (HF gpt2: resid/embd/attn
    # pdrop 0.1, but 0.0 default here for bench parity with the other rows)
    module = gpt2.gpt2_124m(dtype=dtype, param_dtype=param_dtype, remat=remat,
                            max_seq_len=max(seq_len, 1024), sp=sp,
                            dropout=dropout,
                            attn_impl=attn_impl, logits_dtype=logits_dtype)
    return _lm_bundle(module, gpt2.TP_RULES, seq_len, gpt2.num_params)


@register("gpt2_tiny")
def _gpt2_tiny(*, seq_len, dtype, param_dtype, remat, sp=False, attn_impl="auto",
               dropout=0.0, logits_dtype, **_):
    from pytorch_distributed_training_example_tpu.models import gpt2

    module = gpt2.gpt2_tiny(dtype=dtype, param_dtype=param_dtype, remat=remat,
                            max_seq_len=max(seq_len, 256), sp=sp,
                            dropout=dropout,
                            attn_impl=attn_impl, logits_dtype=logits_dtype)
    return _lm_bundle(module, gpt2.TP_RULES, seq_len, gpt2.num_params)


@register("llama3_8b")
def _llama3_8b(*, seq_len, dtype, param_dtype, remat, remat_policy="nothing",
               sp=False, attn_impl="auto", logits_dtype, **_):
    from pytorch_distributed_training_example_tpu.models import llama

    module = llama.llama3_8b(dtype=dtype, param_dtype=param_dtype, remat=remat,
                             remat_policy=remat_policy,
                             max_seq_len=max(seq_len, 8192), sp=sp,
                             attn_impl=attn_impl, logits_dtype=logits_dtype)
    return _lm_bundle(module, llama.TP_RULES, seq_len, llama.num_params)


@register("llama_400m")
def _llama_400m(*, seq_len, dtype, param_dtype, remat, remat_policy="nothing",
                sp=False, attn_impl="auto", logits_dtype, **_):
    from pytorch_distributed_training_example_tpu.models import llama

    module = llama.llama_400m(dtype=dtype, param_dtype=param_dtype,
                              remat=remat, remat_policy=remat_policy,
                              max_seq_len=max(seq_len, 2048),
                              sp=sp, attn_impl=attn_impl,
                              logits_dtype=logits_dtype)
    return _lm_bundle(module, llama.TP_RULES, seq_len, llama.num_params)


@register("llama_tiny")
def _llama_tiny(*, seq_len, dtype, param_dtype, remat, remat_policy="nothing",
                sp=False, attn_impl="auto", logits_dtype, **_):
    from pytorch_distributed_training_example_tpu.models import llama

    module = llama.llama_tiny(dtype=dtype, param_dtype=param_dtype, remat=remat,
                              remat_policy=remat_policy,
                              max_seq_len=max(seq_len, 256), sp=sp,
                              attn_impl=attn_impl, logits_dtype=logits_dtype)
    return _lm_bundle(module, llama.TP_RULES, seq_len, llama.num_params)


def _granite_hybrid(make):
    """Registry builder for the Granite hybrid family (Mamba-2 mixers among
    GQA attention): ``make(granite_hybrid, **kw)`` returns the module. The
    family has ``dp``/``fsdp`` only: the mixer has no tensor-parallel rule
    table, so ``tp``/``fsdp_tp`` fail in ``strategy_rules`` and ``*_sp``
    fails here, rather than replicating silently."""
    def build(*, seq_len, dtype, param_dtype, remat, remat_policy="nothing",
              sp=False, attn_impl="auto", logits_dtype, **_):
        from pytorch_distributed_training_example_tpu.models import (
            granite_hybrid)

        if sp:
            raise ValueError(
                "the Granite hybrid family has no tensor- or sequence-"
                "parallel rules for its Mamba mixer; use strategy dp or fsdp")
        module = make(granite_hybrid, dtype=dtype, param_dtype=param_dtype,
                      remat=remat, remat_policy=remat_policy,
                      attn_impl=attn_impl, logits_dtype=logits_dtype)
        return ModelBundle(
            module=module, task="lm",
            input_template=(jnp.zeros((2, seq_len), jnp.int32),),
            fwd_flops_per_example=seq_len
            * granite_hybrid.forward_flops_per_token(module, seq_len),
            rules={}, examples_unit="sequences")
    return build


# The published granite-4.0-h-micro; one chip's share of it (its first
# period of ten layers, an eighth of the tied vocabulary: what the one-chip
# benchmark cell trains), made from the first; and a toy for the tests.
_REGISTRY["granite4_h_micro"] = _granite_hybrid(
    lambda g, **kw: g.granite4_h_micro(**kw))
_REGISTRY["granite4_h_micro_share"] = _granite_hybrid(
    lambda g, **kw: g.chip_share(g.granite4_h_micro(**kw)))
_REGISTRY["granite_hybrid_tiny"] = _granite_hybrid(
    lambda g, **kw: g.granite_hybrid_tiny(**kw))


def _held_experts_family(name, make):
    """Registry builder for a family whose expert layers are told which
    experts they hold (``models/<name>.py``: ``afmoe``, ``smallthinker``,
    ``glm_moe_lite``, ``nemotron_h``, ``lfm2_moe``, ``qwen3_next``,
    ``xing4``):
    ``make(module, **kw)`` returns the model. ``dp``/``fsdp`` only, as the
    Granite hybrid: the expert layer has no exchange, and there is no
    tensor-parallel rule table."""
    def build(*, seq_len, dtype, param_dtype, remat, remat_policy="nothing",
              sp=False, attn_impl="auto", logits_dtype, **_):
        import importlib

        family = importlib.import_module(
            f"pytorch_distributed_training_example_tpu.models.{name}")
        if sp:
            raise ValueError(
                f"the {name} family has no tensor- or sequence-parallel "
                "rules; use strategy dp or fsdp")
        module = make(family, dtype=dtype, param_dtype=param_dtype,
                      remat=remat, remat_policy=remat_policy,
                      attn_impl=attn_impl, logits_dtype=logits_dtype)
        return ModelBundle(
            module=module, task="lm",
            input_template=(jnp.zeros((2, seq_len), jnp.int32),),
            fwd_flops_per_example=seq_len
            * family.forward_flops_per_token(module, seq_len),
            rules={}, examples_unit="sequences")
    return build


# The published Trinity-Mini; one chip's share of it (an eighth of every
# layer's routed experts and of the vocabulary, a leading dense layer and the
# first period of expert layers: what the one-chip benchmark cell trains),
# made from the first; and a toy for the tests.
_REGISTRY["trinity_mini"] = _held_experts_family(
    "afmoe", lambda a, **kw: a.trinity_mini(**kw))
_REGISTRY["trinity_mini_share"] = _held_experts_family(
    "afmoe", lambda a, **kw: a.chip_share(a.trinity_mini(**kw)))
_REGISTRY["afmoe_tiny"] = _held_experts_family(
    "afmoe", lambda a, **kw: a.afmoe_tiny(**kw))

# The published SmallThinker-21BA3B; one chip's share of it (a quarter of
# every layer's experts and of the vocabulary, the first period of four
# layers: what the one-chip benchmark cell trains); and a toy for the tests.
_REGISTRY["smallthinker_21b"] = _held_experts_family(
    "smallthinker", lambda m, **kw: m.smallthinker_21b(**kw))
_REGISTRY["smallthinker_21b_share"] = _held_experts_family(
    "smallthinker", lambda m, **kw: m.chip_share(m.smallthinker_21b(**kw)))
_REGISTRY["smallthinker_tiny"] = _held_experts_family(
    "smallthinker", lambda m, **kw: m.smallthinker_tiny(**kw))

# The published GLM-4.7-Flash; one chip's share of it (an eighth of every
# layer's routed experts and of the vocabulary, the leading dense layer, four
# expert layers and the MTP layer: what the one-chip benchmark cell trains);
# and a toy for the tests.
_REGISTRY["glm47_flash"] = _held_experts_family(
    "glm_moe_lite", lambda m, **kw: m.glm47_flash(**kw))
_REGISTRY["glm47_flash_share"] = _held_experts_family(
    "glm_moe_lite", lambda m, **kw: m.chip_share(m.glm47_flash(**kw)))
_REGISTRY["glm_moe_lite_tiny"] = _held_experts_family(
    "glm_moe_lite", lambda m, **kw: m.glm_moe_lite_tiny(**kw))


# The published Nemotron-3-Nano-30B-A3B; one chip's share of it (a sixteenth
# of every expert layer's routed experts, an eighth of the vocabulary, the
# published layers 0..8: what the one-chip benchmark cell trains); and a toy
# for the tests.
_REGISTRY["nemotron3_nano"] = _held_experts_family(
    "nemotron_h", lambda m, **kw: m.nemotron3_nano(**kw))
_REGISTRY["nemotron3_nano_share"] = _held_experts_family(
    "nemotron_h", lambda m, **kw: m.chip_share(m.nemotron3_nano(**kw)))
_REGISTRY["nemotron_h_tiny"] = _held_experts_family(
    "nemotron_h", lambda m, **kw: m.nemotron_h_tiny(**kw))


# The published LFM2-8B-A1B; one chip's share of it (a quarter of every
# expert layer's routed experts and of the tied vocabulary, the published
# layers 1..7: what the one-chip benchmark cell trains); and a toy for the
# tests.
_REGISTRY["lfm2_8b_a1b"] = _held_experts_family(
    "lfm2_moe", lambda m, **kw: m.lfm2_8b_a1b(**kw))
_REGISTRY["lfm2_8b_a1b_share"] = _held_experts_family(
    "lfm2_moe", lambda m, **kw: m.chip_share(m.lfm2_8b_a1b(**kw)))
_REGISTRY["lfm2_moe_tiny"] = _held_experts_family(
    "lfm2_moe", lambda m, **kw: m.lfm2_moe_tiny(**kw))


# The published Qwen3-Next-80B-A3B; one chip's share of it (a sixteenth of
# every layer's routed experts, an eighth of the vocabulary, the published
# layers 0..3: what the one-chip benchmark cell trains); and a toy for the
# tests.
_REGISTRY["qwen3_next_80b"] = _held_experts_family(
    "qwen3_next", lambda m, **kw: m.qwen3_next_80b(**kw))
_REGISTRY["qwen3_next_80b_share"] = _held_experts_family(
    "qwen3_next", lambda m, **kw: m.chip_share(m.qwen3_next_80b(**kw)))
_REGISTRY["qwen3_next_tiny"] = _held_experts_family(
    "qwen3_next", lambda m, **kw: m.qwen3_next_tiny(**kw))


# The published Xing4.0-29B-A4B (its 40 layers: the prediction layer behind
# them is not built, ``models/xing4.py`` says why); one chip's share of it (an
# eighth of every layer's routed experts and of the vocabulary, one leading
# dense layer and four expert layers: what the one-chip benchmark cell
# trains); and a toy for the tests.
_REGISTRY["xing4_29b"] = _held_experts_family(
    "xing4", lambda m, **kw: m.xing4_29b(mtp_layers=0, **kw))
_REGISTRY["xing4_29b_share"] = _held_experts_family(
    "xing4", lambda m, **kw: m.chip_share(m.xing4_29b(**kw)))
_REGISTRY["xing4_tiny"] = _held_experts_family(
    "xing4", lambda m, **kw: m.xing4_tiny(**kw))


@register("resnet_micro")
def _resnet_micro(*, num_classes, image_size, dtype, param_dtype, **_):
    from pytorch_distributed_training_example_tpu.models import resnet

    module = resnet.resnet_micro(num_classes=num_classes, dtype=dtype,
                                 param_dtype=param_dtype)
    return ModelBundle(
        module=module, task="classification",
        input_template=(jnp.zeros((2, image_size, image_size, 3), jnp.float32),),
        fwd_flops_per_example=resnet.flops_per_image("resnet_micro", image_size),
        rules={},
    )


def _resnet_bundle(name):
    """Torchvision-style ResNet family entries (reference model zoo:
    ``torchvision.models.resnet{18,34,50,101,152}()``)."""
    def build(*, num_classes, image_size, dtype, param_dtype, **_):
        from pytorch_distributed_training_example_tpu.models import resnet

        module = getattr(resnet, name)(num_classes=num_classes, dtype=dtype,
                                       param_dtype=param_dtype,
                                       small_images=image_size <= 64)
        return ModelBundle(
            module=module, task="classification",
            input_template=(jnp.zeros((2, image_size, image_size, 3),
                                      jnp.float32),),
            fwd_flops_per_example=resnet.flops_per_image(name, image_size),
            rules={},
        )
    return build


for _name in ("resnet18", "resnet34", "resnet50", "resnet101", "resnet152"):
    _REGISTRY[_name] = _resnet_bundle(_name)


@register("vit_l16")
def _vit_l16(*, num_classes, image_size, dtype, param_dtype, remat,
             attn_impl="auto", dropout=0.0, **_):
    from pytorch_distributed_training_example_tpu.models import vit

    module = vit.vit_l16(num_classes=num_classes, dtype=dtype,
                         param_dtype=param_dtype, remat=remat,
                         dropout=dropout, attn_impl=attn_impl)
    return ModelBundle(
        module=module, task="classification",
        input_template=(jnp.zeros((2, image_size, image_size, 3), jnp.float32),),
        fwd_flops_per_example=vit.flops_per_image(image_size, 16, 24, 1024,
                                                  4096),
        rules={"fsdp_tp": vit.TP_RULES, "tp": vit.TP_RULES},
    )
