"""End-to-end pipeline-parallel language-model training (strategy "pp").

Wires the generic GPipe schedule (parallel/pipeline.py) into the Llama
family: a ``scan_layers`` Llama owns ONE stacked block parameter tree
``[num_layers, ...]``; for PP we shard that leading dim over the ``stage``
mesh axis (each chip holds a contiguous slice of layers) and run the
embed -> pipeline(blocks) -> norm -> head forward with microbatched
activations hopping stage-to-stage via ``ppermute``.

The wrapper quacks like a flax module (``init``/``apply``) so the standard
train step, checkpointing, and Trainer work unchanged; its params ARE the
scan-Llama params (checkpoint-compatible with the non-PP model).
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from pytorch_distributed_training_example_tpu.models import llama as llama_lib
from pytorch_distributed_training_example_tpu.parallel import pipeline as pp

#: Parameter rules for strategy "pp": the stacked block tree shards its
#: leading (layer) dim over 'stage'; embeddings/head replicate (they run
#: outside the pipeline on every chip) with auto-FSDP composition available.
PP_RULES = (
    (r"blocks/block/", P("stage")),
    (r".*", "AUTO_FSDP"),
)


class PipelinedLlama:
    """Flax-compatible facade over Llama(scan_layers=True) + GPipe."""

    def __init__(self, module: llama_lib.Llama, mesh: Mesh,
                 num_microbatches: int = 8):
        if not module.scan_layers:
            module = module.clone(scan_layers=True)
        self.module = module
        self.mesh = mesh
        self.num_microbatches = num_microbatches
        self.num_stages = mesh.shape["stage"]
        if module.num_layers % self.num_stages:
            raise ValueError(
                f"num_layers {module.num_layers} must divide by stage "
                f"{self.num_stages}")

    # -- flax-like surface ------------------------------------------------

    def init(self, rngs, tokens, train=False):
        return self.module.init(rngs, tokens, train=train)

    def apply(self, variables, tokens, train=True, rngs=None, mutable=()):
        logits = self._forward(variables["params"], tokens, train)
        if mutable:
            return logits, {}
        return logits

    # -- forward ----------------------------------------------------------

    def _forward(self, params, tokens, train):
        m = self.module
        x = nn.Embed(m.vocab_size, m.d_model, dtype=m.dtype,
                     param_dtype=m.param_dtype).apply(
            {"params": params["embed"]}, tokens)

        block = llama_lib.LlamaBlock(
            num_heads=m.num_heads, num_kv_heads=m.num_kv_heads,
            head_dim=m.head_dim, ffn_dim=m.ffn_dim, rope_theta=m.rope_theta,
            dtype=m.dtype, param_dtype=m.param_dtype, attn_impl="xla")
        if m.remat:
            block_apply = jax.checkpoint(
                lambda p, x: block.apply({"params": p}, x, train),
                policy=jax.checkpoint_policies.nothing_saveable,
                prevent_cse=False)
        else:
            block_apply = lambda p, x: block.apply({"params": p}, x, train)

        S = self.num_stages
        stacked = params["blocks"]["block"]          # leaves [L, ...]
        stage_params = jax.tree.map(
            lambda p: p.reshape(S, p.shape[0] // S, *p.shape[1:]), stacked)

        def stage_fn(p_stage, x):
            def body(x, p_layer):
                return block_apply(p_layer, x), None
            x, _ = jax.lax.scan(body, x, p_stage)
            return x

        # remat_stages stays off: with m.remat the per-block checkpoint above
        # already bounds saved residuals to layer inputs (stage-level remat on
        # top would only re-recompute the scan).
        x = pp.pipeline_apply(stage_fn, stage_params, x, mesh=self.mesh,
                              num_microbatches=self.num_microbatches)

        x = llama_lib.RMSNorm(dtype=m.dtype, param_dtype=m.param_dtype).apply(
            {"params": params["final_norm"]}, x)
        logits = nn.Dense(m.vocab_size, use_bias=False, dtype=m.dtype,
                          param_dtype=m.param_dtype).apply(
            {"params": params["lm_head"]}, x)
        return logits.astype(m.logits_dtype)
