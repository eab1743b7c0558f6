import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_distributed_training_example_tpu.models import registry


@pytest.mark.parametrize("name,size,classes", [
    ("resnet18", 32, 10),
    ("resnet50", 64, 100),
])
def test_resnet_forward_shapes(name, size, classes):
    bundle = registry.create_model(name, num_classes=classes, image_size=size,
                                   dtype=jnp.float32, param_dtype=jnp.float32)
    x = jnp.zeros((4, size, size, 3))
    variables = bundle.module.init(jax.random.PRNGKey(0), x, train=False)
    logits = bundle.module.apply(variables, x, train=False)
    assert logits.shape == (4, classes)
    assert logits.dtype == jnp.float32
    # train mode mutates batch_stats
    logits2, mutated = bundle.module.apply(
        variables, x, train=True, mutable=["batch_stats"],
        rngs={"dropout": jax.random.PRNGKey(1)})
    assert "batch_stats" in mutated


def test_vit_dropout_plumbed_and_defaults_off():
    """Reference parity: torchvision vit_b_16 defaults to dropout=0.0. The
    rate must flow from create_model to the module."""
    off = registry.create_model("vit_b16", num_classes=10)
    assert off.module.dropout == 0.0
    on = registry.create_model("vit_b16", num_classes=10, dropout=0.1)
    assert on.module.dropout == 0.1


def test_dropout_rejected_for_families_without_it():
    """ADVICE r4: builders that have no dropout knob (Llama, ResNet —
    matching their reference factories) must fail loudly on a nonzero
    --dropout instead of silently swallowing it; GPT-2 implements it and
    must plumb it through."""
    for name in ("llama_tiny", "resnet18"):
        with pytest.raises(ValueError, match="dropout"):
            registry.create_model(name, seq_len=64, dropout=0.1)
    on = registry.create_model("gpt2_tiny", seq_len=64, dropout=0.1)
    assert on.module.dropout == 0.1


@pytest.mark.parametrize("name,options,error,match", [
    ("llama_tiny", {"dropout": 0.1}, ValueError, "does not implement dropout"),
    ("gpt2_tiny", {"remat_policy": "dots"}, ValueError,
     "does not implement remat_policy"),
    ("resnet18", {"dropout": 0.0, "remat_policy": "nothing", "sp": True,
                  "attn_impl": "flash"}, None, None),
])
def test_create_model_refuses_what_it_refused(name, options, error, match):
    """The registry's edge is what it was when ``create_model`` spelt every
    option out: a dropout or remat policy given to a family without one
    fails loudly; the value that asks for nothing, and the options another
    family takes, pass (the Trainer hands every model the whole of
    ``Config.model_options``)."""
    if error is None:
        registry.create_model(name, seq_len=32, **options)
        return
    with pytest.raises(error, match=match):
        registry.create_model(name, seq_len=32, **options)


@pytest.mark.parametrize("name", registry.list_models())
def test_no_moe_option_reaches_any_model(name):
    """The expert layer has no options: ``moe_dispatch_impl`` (an option of
    the expert layer that is gone, which every family once accepted and all
    but two ignored) is refused by name for every registered model, as any
    keyword outside ``_OPTIONS`` is."""
    with pytest.raises(TypeError, match="unexpected option 'moe_dispatch_impl'"):
        registry.create_model(name, seq_len=32, moe_dispatch_impl="sort")


@pytest.mark.parametrize("name,expected_m", [
    ("resnet34", 21.80), ("resnet101", 44.55), ("resnet152", 60.19),
    ("vit_l16", 304.33),
])
def test_param_counts_extended_zoo(name, expected_m):
    """New zoo entries match the torchvision factories' published param
    counts (resnet34/101/152, vit_l_16) within 1%."""
    bundle = registry.create_model(name, num_classes=1000, image_size=224)
    variables = jax.eval_shape(
        lambda: bundle.module.init(jax.random.PRNGKey(0),
                                   jnp.zeros((1, 224, 224, 3)), train=False))
    n = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(variables["params"]))
    assert abs(n / 1e6 - expected_m) / expected_m < 0.01, n


def test_param_count_resnet18():
    bundle = registry.create_model("resnet18", num_classes=1000, image_size=224,
                                   dtype=jnp.float32, param_dtype=jnp.float32)
    variables = jax.eval_shape(
        lambda: bundle.module.init(jax.random.PRNGKey(0),
                                   jnp.zeros((1, 224, 224, 3)), train=False))
    n = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(variables["params"]))
    # torchvision resnet18 has 11.69M params
    assert 11.4e6 < n < 12.0e6, n


def test_bf16_compute_fp32_params():
    bundle = registry.create_model("resnet18", num_classes=10, image_size=32,
                                   dtype=jnp.bfloat16, param_dtype=jnp.float32)
    x = jnp.zeros((2, 32, 32, 3))
    variables = bundle.module.init(jax.random.PRNGKey(0), x, train=False)
    for p in jax.tree.leaves(variables["params"]):
        assert p.dtype == jnp.float32
    logits = bundle.module.apply(variables, x, train=False)
    assert logits.dtype == jnp.float32  # outputs cast back up
