"""The main path's kernels compile for the chip — checked without the chip.

The TPU compiler is installed in the sandbox and compiles for a DESCRIBED
v5e:2x2 topology (nothing runs; these are not chip results). That catches
what interpret mode cannot: a kernel over its scoped-VMEM limit, a slice off
the tiling, a Mosaic call GSPMD cannot partition. Real widths, a second or
two each (the grouped FFN ~10 s); whole-step compiles stay out of the suite.

Rules this file keeps (xdist runs several workers, each imports every test
file, and only one process may own libtpu): the topology is described inside
a module-scoped fixture that skips when it cannot be, never at import;
shardings and meshes are built in fixtures/tests; compiles run in this
process with the persistent cache off; code that asks
``jax.default_backend()`` is steered by monkeypatch, not by a program option.
"""

import functools
import os
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from pytorch_distributed_training_example_tpu.core import mesh as mesh_lib
from pytorch_distributed_training_example_tpu.ops import (
    attention as attn, flash_attention as fa, grouped_matmul)

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks"))
import moe_rows_micro  # noqa: E402  (reads a compiled text's row gathers)

BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def topo():
    import os

    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """Sharding on the first described chip, with the persistent compile
    cache off for the module: an entry compiled for a described device can
    be written but not read back without a chip."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", True)
    cc.reset_cache()


@pytest.fixture
def as_tpu(monkeypatch):
    """The program's "are we on the chip" (ops/backend.py) answers tpu."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _compiled_text(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


def _sds(shape, sharding, dtype=BF16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _grads(fn):
    def total(*args):
        return fn(*args).astype(jnp.float32).sum()
    return jax.grad(total, argnums=(0, 1, 2))


def _pallas_calls(fn, *args):
    """{name: (grid, scalar-prefetch operands)} of the kernels ``fn`` calls,
    the jitted launchers' among them."""
    found = {}

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                grid = eqn.params["grid_mapping"]
                found[eqn.params["name"]] = (grid.grid, grid.num_index_operands)
            for value in eqn.params.values():
                if hasattr(value, "jaxpr"):
                    walk(value.jaxpr)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return found


@pytest.mark.parametrize("dtype", [BF16, jnp.float32],
                         ids=["bf16", "fp32"])  # --precision bf16 / fp32
@pytest.mark.parametrize("B,S,H,Hkv,D", [
    (24, 1024, 12, 12, 64),    # GPT-2 124M at the smoke's batch
    (4, 2048, 12, 12, 64),     # two heads' [S, 128] blocks are over VMEM
    (8, 1024, 16, 4, 128),     # causal kernels at D=128, GQA
    (4, 2048, 16, 16, 128),    # D=128: causal forward, chunked backward
    (4, 2048, 8, 2, 128),      # the same under GQA
    (1, 4096, 8, 2, 128),      # largest S the streaming backward admits
    (1, 8192, 8, 2, 128),      # llama3_8b's S: refused there -> online bwd
    (1, 4096, 32, 8, 64),      # granite-4.0-h-micro's attention layer
    (1, 8192, 20, 20, 256),    # GLM-4.7-Flash's expanded latent attention:
    (1, 4096, 20, 20, 256),    # the backward's blocks follow the head width
])
def test_flash_fwd_bwd_compiles(one_chip, B, S, H, Hkv, D, dtype):
    q = _sds((B, S, H, D), one_chip, dtype)
    kv = _sds((B, S, Hkv, D), one_chip, dtype)
    text = _compiled_text(
        _grads(lambda q, k, v: fa.flash_attention(q, k, v, True)), q, kv, kv)
    # The causal kernels are in the text exactly where dispatch plans them
    # for this dtype: the v5e compiler has accepted their whole-head blocks
    # (float32 blocks are twice the bytes, and keep the earlier kernels).
    plans = [fa._auto_causal_plan("auto", True, None, S, S, H, D, dtype,
                                  bwd=bwd) for bwd in (False, True)]
    assert [name in text for name in ("flash_fwd_causal", "flash_bwd_causal")
            ] == [plan is not None for plan in plans]
    if dtype == jnp.float32:
        assert plans == [None, None]
    elif (S, D) == (1024, 64):  # GPT-2's shape: both, and nothing else
        assert plans == [(2, 128), (4, 128)]
        assert "flash_fwd_online" not in text
        assert "flash_bwd_oneshot" not in text
    if (S, H, D) == (4096, 32, 64):
        # no causal or one-shot plan at S=4096, no streaming backward at
        # D=64: the online forward and the two-kernel online backward
        assert all(name in text for name in (
            "flash_fwd_online", "flash_bwd_dq", "flash_bwd_dkv"))
    if S == 8192:
        # The v5e compiler counts the streaming backward over its 16 MB of
        # scoped VMEM here; the planner must not admit it.
        assert fa._stream_bwd_plan(H, S, S, D) is None
    if D == 256:
        # no causal, one-shot or streaming plan at this width: the online
        # three, at the blocks the rule gives (at the defaults the compiler
        # counts flash_bwd_dkv 36 KB over its scoped VMEM at S=8192 in bf16,
        # flash_bwd_dq 6 MB over in float32 at S=4096, and refuses the
        # float32 forward at S=8192)
        assert all(name in text for name in (
            "flash_fwd_online", "flash_bwd_dq", "flash_bwd_dkv"))
        assert "flash_bwd_oneshot" not in text
        item = jnp.dtype(dtype).itemsize
        want = ([(1024, 1024), (1024, 512)] if dtype == BF16
                else [(1024, 512), (512, 512)])
        if S == 8192:   # the backward's row of the table, read on the chip
            want[1] = (512, 1024)
            assert fa.ONLINE_BLOCK_TABLE[True, 8192, 256] == want[1]
        assert [fa._online_blocks(bwd, S, D, 1024, 1024, item)
                for bwd in (False, True)] == want
    else:
        # the four accepted cells' widths: the plans are what they were
        for bwd in (False, True):
            assert fa._online_blocks(bwd, S, D, 1024, 1024,
                                     jnp.dtype(dtype).itemsize) == (1024, 1024)


def _entry_instructions(text):
    """{name: (shape, opcode, operand names)} of the entry computation."""
    import re

    entry = text[text.index("ENTRY "):]
    found = {}
    for line in entry.splitlines():
        m = re.match(r"\s*(?:ROOT )?(%[\w.-]+) = (\(.*?\)|\S+) ([\w-]+)"
                     r"\((.*?)\)(?:,|$)", line)
        if m:
            found[m.group(1)] = (re.sub(r"\{[^}]*\}", "", m.group(2)),
                                 m.group(3), re.findall(r"%[\w.-]+", m.group(4)))
    return found


def test_gpt2_attention_operands_are_lane_dense(one_chip, as_tpu):
    """GPT-2's attention layer at the cell's shape (B24 S1024 H12 D64),
    forward and backward: the projections are flat matmuls whose fusions
    write [24, 1024, 768] straight into the causal kernels and read their
    results, with no copy or transpose between, no operand whose minor
    dimension is a head's 64 or LSE_LANES' 8 (both pad to 128 lanes in
    HBM), and lse at half the bytes of a padded row a head."""
    from pytorch_distributed_training_example_tpu.models import gpt2

    B, S, H, D = 24, 1024, 12, 64
    module = gpt2.SelfAttention(H, BF16, jnp.float32, attn_impl="flash")
    x = _sds((B, S, H * D), one_chip)
    params = jax.tree.map(
        lambda a: _sds(a.shape, one_chip, a.dtype),
        jax.eval_shape(lambda: module.init(
            jax.random.PRNGKey(0), jnp.zeros((1, S, H * D), BF16), False)))

    def grads(params, x, g):
        _, vjp = jax.vjp(lambda p, x: module.apply(p, x, False), params, x)
        return vjp(g)

    ins = _entry_instructions(_compiled_text(grads, params, x, x))
    calls = {name: ins[name] for name in ins
             if ins[name][1] == "custom-call" and name.startswith("%flash_")}
    assert sorted(n.split(".")[0] for n in calls) == [
        "%flash_bwd_causal", "%flash_fwd_causal"]
    dense, lse = f"bf16[{B},{S},{H * D}]", f"f32[{B},{H // 2},{S},128]"
    moves = {"get-tuple-element", "bitcast", "copy-start", "copy-done"}
    users = {}
    for name, (_, _, operands) in ins.items():
        for operand in operands:
            users.setdefault(operand, []).append(name)

    def producer(name):  # through views and XLA's own prefetches
        while ins[name][1] in moves:
            name = ins[name][2][0]
        return name

    def consumers(name):
        for user in users.get(name, []):
            if ins[user][1] in moves:
                yield from consumers(user)
            else:
                yield user

    for name, (shape, _, operands) in calls.items():
        shapes = [ins[o][0] for o in operands] + shape.strip("()").split(", ")
        assert set(shapes) <= {dense, lse}, (name, shapes)
        assert shapes.count(lse) == 1
        around = [producer(o) for o in operands] + list(consumers(name))
        assert around and all(
            ins[n][1] in ("fusion", "custom-call", "reduce", "slice-start")
            for n in around), [(n, ins[n][1]) for n in around]


def _granite_scan_args(sharding, b=1, dtype=BF16):
    """granite-4.0-h-micro's mixer at sequences of 4096: 64 heads of 64,
    state 128 (x, dt, A, B, C, D)."""
    S, H, Pd, N = 4096, 64, 64, 128
    f32 = jnp.float32
    rep = sharding if b == 1 else NamedSharding(sharding.mesh, P())
    return (_sds((b, S, H, Pd), sharding, dtype), _sds((b, S, H), sharding, f32),
            _sds((H,), rep, f32), _sds((b, S, N), sharding, dtype),
            _sds((b, S, N), sharding, dtype), _sds((H,), rep, f32))


def _scan_grads():
    from pytorch_distributed_training_example_tpu.ops import ssd

    total = lambda *a: ssd.ssd(*a, chunk=256).astype(jnp.float32).sum()
    return ssd, jax.jit(jax.grad(total, argnums=(0, 1, 2, 3, 4, 5)))


def test_ssd_scan_compiles_at_published_widths(one_chip, monkeypatch):
    """The ``jax.numpy`` scan, which shapes the plan refuses still take, at
    chunk 256, bf16 operands and float32 decays. Plain XLA (no Mosaic call);
    the compiler counts its temporaries."""
    ssd, grads = _scan_grads()
    monkeypatch.setattr(ssd, "_kernel_plan", lambda *a: None)
    compiled = grads.lower(*_granite_scan_args(one_chip)).compile()
    assert "tpu_custom_call" not in compiled.as_text()
    # a handful of [16, 64, 256, 256] tiles (268 MB in float32) and no more
    assert compiled.memory_analysis().temp_size_in_bytes < 3 * 2 ** 30


@pytest.mark.parametrize("dtype", [BF16, jnp.float32], ids=["bf16", "fp32"])
def test_ssd_kernels_compile_at_published_widths(one_chip, as_tpu, dtype):
    """The kernel pair at the same shape, forward and all six cotangents:
    inside the 16 MB of scoped VMEM at the plan's group, both kernels in the
    program by name, and no [16, 64, 256, 256] float32 tile left in HBM (the
    chunk states, y and the cotangents are what ``temp`` holds)."""
    ssd, grads = _scan_grads()
    args = _granite_scan_args(one_chip, dtype=dtype)
    forward = jax.jit(lambda *a: ssd.ssd(*a, chunk=256)).lower(*args).compile()
    assert "ssd_fwd" in forward.as_text()
    compiled = grads.lower(*args).compile()
    text = compiled.as_text()
    assert "ssd_fwd" in text and "ssd_bwd" in text
    assert "64,256,256]" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 29


def test_padded_flash_vit_compiles(one_chip):
    x = _sds((64, 197, 12, 64), one_chip)  # ViT-B/16
    text = _compiled_text(_grads(attn.padded_flash_attention), x, x, x)
    # non-causal with kv_len: the one-shot kernels, as before the causal ones
    assert "flash_fwd_oneshot" in text and "flash_bwd_oneshot" in text
    assert "_causal" not in text


@pytest.mark.parametrize("H,Hkv,D", [(32, 8, 128), (12, 12, 64)])
def test_paged_decode_compiles(one_chip, as_tpu, H, Hkv, D):
    B, page, pages, max_pages = 8, 16, 2048, 64
    pool = _sds((pages, page, Hkv, D), one_chip)
    _compiled_text(
        fa.paged_decode_attention, _sds((B, H, D), one_chip), pool, pool,
        _sds((B, max_pages), one_chip, jnp.int32),
        _sds((B,), one_chip, jnp.int32))


def test_grouped_ffn_fwd_bwd_compiles(one_chip, as_tpu):
    T, E, d, ffn = 16384, 8, 1024, 2048
    seg = _sds((E,), one_chip, jnp.int32)

    def grads(x, w_up, w_down, starts, counts):
        return _grads(lambda x, wu, wd: grouped_matmul.grouped_ffn(
            x, wu, wd, starts, counts))(x, w_up, w_down)

    _compiled_text(grads, _sds((T, d), one_chip), _sds((E, d, ffn), one_chip),
                   _sds((E, ffn, d), one_chip), seg, seg)


#: The gated FFN's kernels a layer, by side of the bounded layout's ``cond``:
#: a forward is ``gated_ffn_up`` and the down projection from its ``gate`` and
#: ``up`` (``gated_ffn_down``); the backward runs the down projection again
#: from them on the whole layout's side alone (the parts' side differentiates a
#: checkpointed part, whose combine is inside it).
_GATED_BACKWARD = ("gated_ffn_dh", "gated_ffn_dx", "gated_ffn_dw_up",
                   "gated_ffn_dw_down")


def _expert_kernel_calls(text):
    """``[(kernel, op_name)]`` of a compiled text's expert kernels."""
    import re

    return [(m.group(1), m.group(2)) for m in re.finditer(
        r"%((?:gated_ffn|grouped_matmul)[a-z_]*)[.\d]* = [^\n]*tpu_custom_call"
        r"[^\n]*op_name=\"([^\"]*)\"", text)]


@pytest.mark.parametrize("rows,d,f,held,act,two_blocks", [
    pytest.param(18432, 2048, 1024, 16, "silu", (1024, 2048), id="trinity"),
    pytest.param(26624, 2560, 768, 16, "relu", (768, 2560), id="smallthinker"),
    pytest.param(9216, 2048, 1536, 8, "silu", (768, 1024), id="glm"),
    pytest.param(17408, 2048, 1792, 8, "silu", (896, 1024), id="lfm2")])
def test_gated_ffn_kernels_compile_at_published_widths(
        one_chip, as_tpu, rows, d, f, held, act, two_blocks):
    """The gated FFN's six kernels at the four gated cells' widths and
    bounded layouts (tiles of 128 rows): the forward, the down projection
    again from ``gate`` and ``up``, and the backward's four, each under the scoped
    VMEM it asks for. The kernels that hold two weight blocks side by side
    (``gated_ffn_up``, ``gated_ffn_dx``: ``two_blocks`` are their column
    blocks) and ``gated_ffn_dw_up``'s two accumulators take more than the
    default scope and say so in their ``gmm_plan``."""
    from pytorch_distributed_training_example_tpu.utils import telemetry

    tiles = tuple(_sds((n,), one_chip, jnp.int32)
                  for n in (rows // 128, rows // 128, 1))
    x, w_in, w_out = (_sds((rows, d), one_chip), _sds((held, d, f), one_chip),
                      _sds((held, f, d), one_chip))

    def every(x, w_gate, w_up, w_down, tiles, dy):
        y, gate, up = grouped_matmul.gated_ffn_padded_kept(
            x, w_gate, w_up, w_down, tiles, act)
        return (y,
                grouped_matmul.gated_down_padded(gate, up, w_down, tiles, act),
                grouped_matmul.gated_ffn_padded_bwd(
                    x, gate, up, w_gate, w_up, w_down, tiles, dy, act))

    mark = len(telemetry.recorder().records())
    text = _compiled_text(every, x, w_in, w_in, w_out, tiles, x)
    for name in ("gated_ffn_up", "gated_ffn_down", *_GATED_BACKWARD):
        assert f"%{name}" in text, name
    assert "%grouped_matmul" not in text
    plans = {r.value["kernel"]: r.value
             for r in telemetry.recorder().records()[mark:]
             if r.name == "gmm_plan" and r.value["form"] == "gated"}
    assert (plans["gated_ffn_up"]["block"],
            plans["gated_ffn_dx"]["block"]) == two_blocks
    over = {name for name, plan in plans.items()
            if plan["vmem"] > grouped_matmul._SCOPED_VMEM}
    assert {"gated_ffn_dw_up"} <= over <= {
        "gated_ffn_up", "gated_ffn_dx", "gated_ffn_dw_up"}, plans
    assert {"gated_ffn_up", "gated_ffn_dx"} <= over or f > 1024, plans
    assert all(plan["vmem"] < 2 * grouped_matmul._SCOPED_VMEM
               for plan in plans.values()), plans


def test_flash_under_four_device_mesh_compiles(topo, one_chip, as_tpu):
    """Batch sharded over four chips: GSPMD cannot partition a Mosaic
    kernel, so a bare call raises "wrap the call in a shard_map" — the
    dispatcher must go through mesh_lib.manual_call."""
    mesh = mesh_lib.build_mesh({"fsdp": 4}, devices=topo.devices)
    x = _sds((24, 1024, 12, 64),
             NamedSharding(mesh, P(("data", "fsdp"), None, None, None)))
    with mesh_lib.use_mesh(mesh):
        _compiled_text(
            _grads(lambda q, k, v: attn.attention(q, k, v, causal=True)),
            x, x, x)


def test_ssd_under_four_device_mesh_compiles(topo, one_chip, as_tpu):
    """Four sequences over four chips, the batch the only sharded axis: the
    kernels go through mesh_lib.manual_call, and A's and D's cotangents are
    summed across the chips outside them."""
    mesh = mesh_lib.build_mesh({"fsdp": 4}, devices=topo.devices)
    batch = NamedSharding(mesh, P(("data", "fsdp")))
    _, grads = _scan_grads()
    with mesh_lib.use_mesh(mesh):
        text = grads.lower(*_granite_scan_args(batch, b=4)).compile().as_text()
    assert "ssd_fwd" in text and "ssd_bwd" in text and "all-reduce" in text


def _stage_calls(S, conv_dim, inner, groups, sharding, b=1):
    """``{stage: (function, its operands)}`` of the mixer's two elementwise
    stages at a cell's widths in bf16 (the scan's ``y`` float32)."""
    from pytorch_distributed_training_example_tpu.ops import ssd

    f32 = jnp.float32
    rep = sharding if b == 1 else NamedSharding(sharding.mesh, P())
    return ssd, {
        "conv_silu": (ssd.conv_silu, (
            _sds((b, S, conv_dim), sharding), _sds((4, conv_dim), rep, f32),
            _sds((conv_dim,), rep, f32))),
        "gate_norm": (
            lambda *a: ssd.gate_norm(*a, groups=groups, epsilon=1e-5,
                                     dtype=BF16),
            (_sds((b, S, inner), sharding, f32), _sds((b, S, inner), sharding),
             _sds((inner,), rep, f32)))}


@pytest.mark.parametrize("S,conv_dim,inner,groups", [
    (8192, 6144, 4096, 8),     # Nemotron-3-Nano's mixer at one 8,192 sequence
    (4096, 4352, 4096, 1),     # granite-4.0-h-micro's at one of 4,096
], ids=["nemotron", "granite"])
def test_mixer_stage_kernels_compile_at_the_cells_widths(
        one_chip, as_tpu, S, conv_dim, inner, groups):
    """``conv_silu`` (K 4) and ``gate_norm``, forward and every cotangent, in
    bf16: the plan admits both, each kernel is in its program by name inside
    the scoped VMEM, and nothing the size of the operands is left in HBM
    beside them (no float32 copy of ``xBC``, no padded cotangent)."""
    ssd, calls = _stage_calls(S, conv_dim, inner, groups, one_chip)
    assert ssd._stage_plan("conv_silu", S, conv_dim, 1, BF16, 4)
    assert ssd._stage_plan("gate_norm", S, inner, groups, BF16)
    for stage, (fn, args) in calls.items():
        forward = jax.jit(fn).lower(*args).compile()
        assert stage + "_fwd" in forward.as_text()
        assert forward.memory_analysis().temp_size_in_bytes < 2 ** 20
        total = lambda *a: fn(*a).astype(jnp.float32).sum()
        backward = jax.jit(jax.grad(total, argnums=(0, 1, 2))).lower(
            *args).compile()
        assert stage + "_bwd" in backward.as_text()
        # the cotangent handed in (ones, as the sum's) and the small sums
        assert backward.memory_analysis().temp_size_in_bytes < (
            S * max(conv_dim, inner) * 2 * 1.1)


def _delta_net_args(one_chip, S=8192, K=2048, V=4096):
    """Shapes of the delta-rule mixer's output stage at the published widths:
    the rule's float32 ``o`` [1, S, 32 x 128], ``qkvz`` [1, S, 12288] whose
    last 4,096 lanes are ``z`` (lane 8192 on), one scale of 128."""
    return (_sds((1, S, V), one_chip, jnp.float32),
            _sds((1, S, 2 * K + 2 * V), one_chip),
            _sds((V // 32,), one_chip, jnp.float32))


def test_norm_gate_kernels_compile_at_qwen3_next_widths(one_chip, as_tpu):
    """``gate_norm``'s pair in the other order at S 8192, 32 heads of 128,
    ``z`` read at lane 8192 of ``qkvz``'s 12,288: a tile of [256, 512] (four
    heads a block, sixteen blocks in), each kernel in its program by its own
    name and no ``gate_norm_*`` beside it, the source itself the call's
    operand (no ``bf16[1,8192,4096]`` slice made of it), and nothing the size
    of the operands left in HBM beside them."""
    from pytorch_distributed_training_example_tpu.ops import ssd

    assert ssd._stage_plan("norm_gate", 8192, 4096, 32, BF16) == (256, 512)
    fn = lambda o, qkvz, scale: ssd.norm_gate(
        o, qkvz[..., 8192:], scale, groups=32, epsilon=1e-6, dtype=BF16,
        source=qkvz, offset=8192)
    args = _delta_net_args(one_chip)
    forward = jax.jit(fn).lower(*args).compile()
    text = forward.as_text()
    assert "%norm_gate_fwd" in text and "%gate_norm_fwd" not in text
    assert " slice(" not in text
    assert forward.memory_analysis().temp_size_in_bytes < 2 ** 20
    total = lambda *a: fn(*a).astype(jnp.float32).sum()
    backward = jax.jit(jax.grad(total, argnums=(0, 1, 2))).lower(
        *args).compile()
    text = backward.as_text()
    assert "%norm_gate_bwd" in text and "%gate_norm_bwd" not in text
    assert not [line for line in text.splitlines()
                if " slice(" in line and "bf16[1,8192,4096]" in line]
    # the cotangent handed in (ones, as the sum's), dz before it is padded
    # into qkvz's cotangent, and the small sums
    assert backward.memory_analysis().temp_size_in_bytes < (
        2 * 8192 * 4096 * 2 * 1.1)


def test_delta_rule_output_meets_its_norm_without_a_change_of_layout(
        one_chip, as_tpu):
    """One delta-rule mixer's rule and output stage, forward and backward, as
    ``models/qwen3_next.GatedDeltaNet`` chains them: ``o`` goes from
    ``delta_rule_fwd`` to ``norm_gate_fwd`` and ``do`` from ``norm_gate_bwd``
    to ``delta_rule_bwd`` as ``f32[1,8192,4096]``, and no float32 ``copy`` of
    a ``[..., 32, 128]`` shape (8 heads x 128 lanes a tile where the kernels'
    layout is 8 tokens x 128 lanes: 134 MB re-laid out each way, which the
    ``jax.numpy`` norm's reshape cost a layer) is left in the program."""
    import re

    from pytorch_distributed_training_example_tpu.ops import gated_delta, ssd

    def layer(q, k, v, g, beta, qkvz, scale):
        o = gated_delta.gated_delta_rule(q, k, v, g, beta)
        b, S = o.shape[:2]
        y = ssd.norm_gate(o.reshape(b, S, -1), qkvz[..., 8192:], scale,
                          groups=32, epsilon=1e-6, dtype=BF16, source=qkvz,
                          offset=8192)
        return y.astype(jnp.float32).sum()

    qk = _sds((1, 8192, 16, 128), one_chip)
    v = _sds((1, 8192, 32, 128), one_chip)
    gates = _sds((1, 8192, 32), one_chip, jnp.float32)
    _, qkvz, scale = _delta_net_args(one_chip)
    text = _compiled_text(jax.value_and_grad(layer, argnums=tuple(range(7))),
                          qk, qk, v, gates, gates, qkvz, scale)
    calls = re.findall(r"%([a-z_]+)[.\d]* = [^\n]*tpu_custom_call", text)
    assert sorted(calls) == ["delta_rule_bwd", "delta_rule_fwd",
                             "norm_gate_bwd", "norm_gate_fwd"], calls
    relaid = [line.strip()[:160] for line in text.splitlines()
              if re.search(r"= f32\[[\d,]*32,128\]\S* copy\(", line)]
    assert not relaid, relaid
    # what the kernels hand each other is the lane-dense array itself
    for name in ("norm_gate_fwd", "norm_gate_bwd", "delta_rule_bwd"):
        line = next(l for l in text.splitlines()
                    if re.search(rf"%{name}[.\d]* = ", l))
        assert "f32[1,8192,4096]{2,1,0}" in line.split(
            "operand_layout_constraints=")[1], name


def test_mixer_stages_under_four_device_mesh_compile(topo, one_chip, as_tpu):
    """Four sequences over four chips: both stages go through
    mesh_lib.manual_call with the batch sharded, and the parameters'
    cotangents are summed across the chips outside the kernels."""
    mesh = mesh_lib.build_mesh({"fsdp": 4}, devices=topo.devices)
    batch = NamedSharding(mesh, P(("data", "fsdp")))
    _, calls = _stage_calls(8192, 6144, 4096, 8, batch, b=4)
    for stage, (fn, args) in calls.items():
        total = lambda *a: fn(*a).astype(jnp.float32).sum()
        with mesh_lib.use_mesh(mesh):
            text = jax.jit(jax.grad(total, argnums=(0, 1, 2))).lower(
                *args).compile().as_text()
        assert stage + "_bwd" in text and "all-reduce" in text, stage


# -- Trinity-Mini's share (models/afmoe.py): the window kernels, the held
# -- experts' grouped matmuls, and the whole step at the benchmark's size


def test_window_flash_compiles_at_published_widths(one_chip):
    """B1 H32/4 S8192 D128 with the published window of 2048: the online
    kernels under the window's schedule and their window names, a walked
    table of 21 steps a head (1 + 2 + 6 x 3 blocks of 1024 x 1024), and none
    of the others; the same call without a window keeps the kernels it had."""
    q = _sds((1, 8192, 32, 128), one_chip)
    kv = _sds((1, 8192, 4, 128), one_chip)
    call = _grads(lambda q, k, v: fa.flash_attention(q, k, v, True,
                                                     window=2048))
    windowed = _compiled_text(call, q, kv, kv)
    for name in fa.WINDOW_KERNELS:
        assert name in windowed, name
    assert "flash_fwd_online" not in windowed
    # the grid is the walked table (its two scalar-prefetch operands first)
    assert _pallas_calls(call, q, kv, kv) == dict.fromkeys(
        fa.WINDOW_KERNELS, ((1, 32, 21), 2))
    full = _compiled_text(_grads(lambda q, k, v: fa.flash_attention(
        q, k, v, True)), q, kv, kv)
    assert "flash_fwd_online" in full and "flash_fwd_window" not in full


def _held_experts_layer(one_chip, num_experts=128, ffn_dim=1024, top_k=8,
                        held=16, route_scale=2.826, d=2048, tokens=8192):
    """``(layer, params, batch_stats, x)``: by default Trinity's 16 held
    experts of 128, 8 a token, 8,192 tokens of 2048, experts of 1024, as
    shapes on the chip."""
    from pytorch_distributed_training_example_tpu.parallel import moe as moe_lib

    layer = moe_lib.SharedExpertMoE(
        num_experts=num_experts, ffn_dim=ffn_dim, top_k=top_k,
        held_experts=(held, 0), shared_ffn_dim=ffn_dim,
        route_scale=route_scale, balance_coeff=0.001,
        dtype=BF16, param_dtype=jnp.float32)
    shapes = jax.eval_shape(lambda: layer.init(
        jax.random.key(0), jnp.zeros((1, tokens, d), BF16), train=False))
    on_chip = lambda tree: jax.tree.map(
        lambda s: _sds(s.shape, one_chip, s.dtype), tree)
    return (layer, on_chip(shapes["params"]), on_chip(shapes["batch_stats"]),
            _sds((1, tokens, d), one_chip))


def _layer_grads_text(layer, *args):
    """The compiled text of a held experts' layer's gradients."""
    def grads(params, stats, x):
        return jax.grad(lambda p, x: layer.apply(
            {"params": p, "batch_stats": stats}, x, train=False).astype(
                jnp.float32).sum(), argnums=(0, 1))(params, x)

    return _compiled_text(grads, *args)


@functools.cache
def _trinity_layer_text(one_chip):
    """Trinity's layer (compiled once for the tests that read it)."""
    return _layer_grads_text(*_held_experts_layer(one_chip))


def test_held_experts_layer_compiles_at_published_widths(one_chip, as_tpu):
    """The held experts' layer at the published widths: the gated grouped
    FFN's kernels forward and backward (the transposed read of the weights in
    dh and dx, the two 4 MB accumulators of dw_gate and dw_up under the
    scoped VMEM their kernel asks for)."""
    text = _trinity_layer_text(one_chip)
    assert "gated_ffn_dw_up" in text and "conditional" in text
    # the bounded layout: 144 tiles of 128 rows, not the worst case's 528
    assert "bf16[18432,2048]" in text and "bf16[67584,2048]" not in text


@functools.cache
def _trinity_remat_text(one_chip):
    """Trinity's layer under the block's remat (``nothing_saveable``), with a
    consumer inside it that needs the layer's output as ``post_ffn_norm``
    does (``moe_rows_micro.layer_grads``, what ``--index-ops`` compiles): the
    gradients' compiled text (compiled once for the tests that read it)."""
    from pytorch_distributed_training_example_tpu.parallel import moe as moe_lib

    grads, args = moe_rows_micro.layer_grads(moe_lib, "trinity_mini", one_chip)
    return _compiled_text(grads, *args)


def test_held_experts_backward_runs_no_routed_forward_again(one_chip, as_tpu):
    """The same layer under the block's remat (``_trinity_remat_text``): on
    the whole layout's side of the ``cond``s (the parts' side lies
    under a ``while``) the gradients hold the recomputation's forward (one
    ``gated_ffn_up`` that writes ``gate`` and ``up`` and the down projection
    from them; the first forward's value nobody asks for here) and the
    backward's five kernels: the down projection again for the combine's
    weights from ``gate`` and ``up``, ``dh`` as ``dgate`` and ``dup``, one
    ``dx``, ``dw_gate`` with ``dw_up``, ``dw_down``. The parts' side runs the
    same kernels (a part's forward, its recomputation and the transposes; its
    combine lies inside the part, so no down projection runs a third time);
    what crosses a ``cond`` is in the compute dtype."""
    import re
    from collections import Counter

    text = _trinity_remat_text(one_chip)
    found = _expert_kernel_calls(text)
    whole = [(name, scope) for name, scope in found if "/while/" not in scope]
    assert Counter(name for name, _ in whole) == {
        "gated_ffn_up": 1, "gated_ffn_down": 2,
        **dict.fromkeys(_GATED_BACKWARD, 1)}, whole
    # the backward's own: no routed forward again (two more were it to
    # differentiate the branch as a whole, the forward inside it)
    backward = Counter(name for name, scope in whole if "transpose(" in scope
                       and "rematted_computation" not in scope)
    assert backward == {"gated_ffn_down": 1,
                        **dict.fromkeys(_GATED_BACKWARD, 1)}, backward
    parts = Counter(name for name, scope in found if "/while/" in scope)
    assert parts == {"gated_ffn_up": 2, "gated_ffn_down": 2,
                     **dict.fromkeys(_GATED_BACKWARD, 1)}, parts
    crossing = re.findall(r" = (\(.*?\)) conditional\(", text)
    assert crossing and not any("f32[18432,1024]" in c for c in crossing)
    assert any("bf16[18432,1024]" in c for c in crossing)   # gate and up
    assert "bf16[18432,2048]" in text and "bf16[67584,2048]" not in text
    # no XLA pass over the whole layout is left between the kernels: the
    # float32 views of gate and up that the gate's fusions made are gone
    assert "f32[18432,1024]" not in text


def test_held_experts_layer_compiles_at_glm_widths(one_chip, as_tpu):
    """GLM-4.7-Flash's expert layer: 8 held experts of 64, 4 a token, 8,192
    tokens of 2048, experts of 1536 (two column blocks of 768), a shared
    expert of 1536; forward and backward, the bounded layout's ``cond``."""
    text = _layer_grads_text(*_held_experts_layer(
        one_chip, num_experts=64, ffn_dim=1536, top_k=4, held=8,
        route_scale=1.8))
    assert "gated_ffn_dw_up" in text and "conditional" in text
    # the bounded layout: 8,192 x 4 pairs, an eighth of them expected here;
    # no layout of the worst case's 32,768 rows and more
    assert "bf16[32768,2048]" not in text and "bf16[33792,2048]" not in text


def _share_step(preset, one_chip, seq_len=8192):
    """``(compiled step, held bytes)`` of ``preset`` at 1 x ``seq_len`` (bf16,
    per-block remat, AdamW, flash attention) for a described v5e chip."""
    from pytorch_distributed_training_example_tpu.core import (
        train_loop, trainer as trainer_lib)
    from pytorch_distributed_training_example_tpu.core.train_state import (
        TrainState)
    from pytorch_distributed_training_example_tpu.utils.config import (
        from_preset)

    cfg = from_preset(preset, global_batch_size=1,
                      seq_len=seq_len, lr_schedule="constant", warmup_epochs=0.0,
                      attn_impl="flash")
    mesh = mesh_lib.build_mesh(dict(data=1, fsdp=1),
                               devices=[one_chip._device])
    bundle = trainer_lib.build_model(cfg)
    program = trainer_lib.build_step_program(cfg, mesh, 100, bundle)
    model = bundle.module

    def init(rng):
        variables = model.init({"params": rng, "dropout": rng},
                               *bundle.input_template, train=False)
        return TrainState.create(
            apply_fn=model.apply, params=variables["params"], tx=program.tx,
            rng=rng, batch_stats=variables.get("batch_stats"), scaler=None)

    shape = jax.eval_shape(init, jax.random.PRNGKey(0))
    shardings = train_loop.state_shardings(shape, mesh, program.rules)
    state = jax.tree.map(lambda s, sh: _sds(s.shape, sh, s.dtype), shape,
                         shardings)
    rows = NamedSharding(mesh, P(("data", "fsdp")))
    batch = {k: _sds((1, seq_len), rows, jnp.int32)
             for k in ("tokens", "targets")}
    with mesh_lib.use_mesh(mesh):
        compiled = program.train_step.lower(state, batch).compile()
    mem = compiled.memory_analysis()
    held = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    return compiled, mem, held


@pytest.mark.slow  # 80 s of the TPU compiler on every core: whole-step
# compiles stay out of the tier-1 suite (this file's docstring); run it by name
def test_trinity_share_step_fits_the_chip(one_chip, as_tpu):
    """The benchmark cell's step (``trinity_mini_share`` at 1 x 8192, bf16,
    per-block remat, AdamW) compiles for a described v5e with its arguments,
    temporaries and unaliased outputs under the chip's memory: 15.58 GB of
    the 16.9 the allocator offers (PERF.md has the chip's own reading)."""
    compiled, mem, held = _share_step("trinity_mini_share", one_chip)
    assert mem.argument_size_in_bytes == pytest.approx(705_473_792 * 12,
                                                       rel=1e-3)
    assert held < 16.0e9, held
    text = compiled.as_text()
    assert _scalar_index_ops(text, 8192, 128, 8) == []
    for name in ("flash_fwd_window", "flash_bwd_window_dq", "flash_fwd_online",
                 "gated_ffn_up", "gated_ffn_down", *_GATED_BACKWARD):
        assert name in text, name
    assert "grouped_matmul" not in text
    # three big moves a layer (the combine, the combine again in the block's
    # remat, whose norm reads it, and the dispatch's transpose), eight slabs
    # each, from one 75 MB source a move that VMEM holds
    slabs = [source for _, rows, source in moe_rows_micro.row_gathers(text)
             if rows == "bf16[8192,2048]"]
    assert len(slabs) == 4 * 3 * 8, len(slabs)
    assert all(source.startswith("bf16[18432,2048]{") and "S(1)" in source
               for source in slabs), set(slabs)


# -- SmallThinker's share (models/smallthinker.py): the window kernels at a
# -- window of 4096 under 28 query heads to 4, ReLU-gated experts of 768
# -- behind a router that ran elsewhere, and the whole step


def test_window_flash_compiles_at_smallthinker_widths(one_chip):
    """B1 H28/4 S8192 D128 (seven query heads a KV head: not a power of two)
    with the published window of 4096 (four 1024-blocks): the three kernels
    under their window names, a walked table of 30 steps a head (1 + 2 + 3 +
    4 + 4 x 5); the same call without a window, the model's position-free
    full layer, takes the online forward and the dq / dkv backward."""
    q = _sds((1, 8192, 28, 128), one_chip)
    kv = _sds((1, 8192, 4, 128), one_chip)
    call = _grads(lambda q, k, v: fa.flash_attention(q, k, v, True,
                                                     window=4096))
    windowed = _compiled_text(call, q, kv, kv)
    for name in fa.WINDOW_KERNELS:
        assert name in windowed, name
    assert "flash_fwd_online" not in windowed
    assert _pallas_calls(call, q, kv, kv) == dict.fromkeys(
        fa.WINDOW_KERNELS, ((1, 28, 30), 2))
    full = _compiled_text(_grads(lambda q, k, v: fa.flash_attention(
        q, k, v, True)), q, kv, kv)
    for name in ("flash_fwd_online", "flash_bwd_dq", "flash_bwd_dkv"):
        assert name in full, name
    assert "flash_fwd_window" not in full


@functools.cache
def _smallthinker_routine_text(one_chip):
    """The compiled text of the held experts' routine alone at SmallThinker's
    widths (16 held of 64, 6 a token, 8,192 tokens of 2560, experts of 768),
    ReLU-gated, over a plan that the model's router made elsewhere (under
    the scope its name gives it in the model, ``moe_router``): forward and
    backward (compiled once for the tests that read it)."""
    from pytorch_distributed_training_example_tpu.parallel import moe as moe_lib

    router = moe_lib.TopKSoftmaxRouter(num_experts=64, top_k=6)
    layer = moe_lib.HeldExperts(ffn_dim=768, held_experts=(16, 0), act="relu",
                                dtype=BF16, param_dtype=jnp.float32)
    r = jnp.zeros((1, 8192, 2560), jnp.float32)

    def shapes():
        kernel = router.init(jax.random.key(0), r)["params"]
        plan = router.apply({"params": kernel}, r)
        return kernel, layer.init(jax.random.key(0), r.astype(BF16),
                                  plan)["params"]

    on_chip = lambda tree: jax.tree.map(
        lambda s: _sds(s.shape, one_chip, s.dtype), tree)
    kernel, params = on_chip(jax.eval_shape(shapes))

    def grads(kernel, params, r, y):
        def total(kernel, params, y):
            # (the outermost scope's name is wrapped by the transforms'
            # own: ``jvp(block)/moe_router``, as under a model's name)
            with jax.named_scope("block"), jax.named_scope("moe_router"):
                plan = router.apply({"params": kernel}, r)
            return layer.apply({"params": params}, y, plan).astype(
                jnp.float32).sum()
        return jax.grad(total, argnums=(0, 1, 2))(kernel, params, y)

    return _compiled_text(grads, kernel, params,
                          _sds((1, 8192, 2560), one_chip, jnp.float32),
                          _sds((1, 8192, 2560), one_chip))


def test_relu_held_experts_compile_at_published_widths(one_chip, as_tpu):
    """That routine compiles (768 columns in one block: six lane tiles that
    fit) in the bounded layout at ``chunks`` 2."""
    text = _smallthinker_routine_text(one_chip)
    assert "gated_ffn_dw_up" in text and "conditional" in text
    # the bounded layout: 208 tiles of 128 rows (half the tokens' worst
    # case), not the whole worst case's 400
    assert "bf16[26624,2560]" in text and "bf16[51200,2560]" not in text
    assert "bf16[26624,768]" in text


#: ``(compiled text, k, d, the padded rows P, the column parts of [P, d])``
EXPERT_TEXTS = pytest.mark.parametrize("text_of,k,d,P,parts", [
    pytest.param(_trinity_layer_text, 8, 2048, 18432, 1, id="trinity"),
    pytest.param(_smallthinker_routine_text, 6, 2560, 26624, 2,
                 id="smallthinker")])


@EXPERT_TEXTS
def test_held_experts_moves_of_rows_are_choice_major(one_chip, as_tpu,
                                                     text_of, k, d, P, parts):
    """The token-side moves at both expert cells' widths (8,192 tokens): no
    row array is viewed ``[tokens, k, d]``, which at k = 6 is a physical
    ``reshape`` that pads six rows to a tile's sublanes; and on the whole
    layout's side the backward gathers ``k`` slabs of ``[8192, d / parts]`` a
    column part for the dispatch's transpose, one float32 ``[P, d]`` for both
    of the combine's cotangents and ``x_pad`` again: no ``[8192 * k, d]``
    gather of ``y_pad`` for the weights' gradient, which is made in the padded
    layout."""
    import re
    from collections import Counter

    text = text_of(one_chip)
    assert not re.findall(r" = \w+\[\d+,%d,%d\]\S* reshape\(" % (k, d), text)
    gathers = Counter(
        (m.group(1), m.group(2).split("/")[-3]) for m in re.finditer(
            r" = (\w+\[[\d,]+\])\S* fusion\([^\n]*kind=kCustom"
            r"[^\n]*op_name=\"([^\"]*/gather)\"", text)
        if "transpose(" in m.group(2) and "/while/" not in m.group(2)
        and m.group(1).endswith((",%d]" % d, ",%d]" % (d // parts))))
    assert gathers == {
        ("bf16[8192,%d]" % (d // parts), "moe_dispatch"): k * parts,
        ("f32[%d,%d]" % (P, d), "moe_combine"): 1,
        ("bf16[%d,%d]" % (P, d), "moe_dispatch"): 1}, gathers


@EXPERT_TEXTS
def test_held_experts_gather_from_sources_vmem_can_hold(
        one_chip, as_tpu, text_of, k, d, P, parts):
    """The whole layout's side gathers its slabs of ``[8192, d / parts]`` from
    sources of ``[P, d / parts]`` under ``GATHER_SOURCE_BYTES``: Trinity's
    ``bf16[18432, 2048]`` (75 MB) stays one source a move, SmallThinker's
    ``bf16[26624, 2560]`` (136 MB, over a v5e's VMEM) is read in two column
    parts and whole by no gather. (These texts are the layer's gradients
    alone, where nothing reads the forward's combine: the dispatch's
    transpose is the move they hold; the steps' own texts are read by the slow
    tests.)"""
    from collections import Counter

    slabs = Counter(
        source.split("{")[0]
        for _, rows, source in moe_rows_micro.row_gathers(text_of(one_chip))
        if rows == "bf16[8192,%d]" % (d // parts))
    assert slabs == {"bf16[%d,%d]" % (P, d // parts): k * parts}, slabs


def _scalar_index_ops(text, T, E, k):
    """What the router and the plan must not hold of a compiled text's
    ``moe_rows_micro.index_ops`` (PR 49): a gather or a scatter under
    ``moe_router`` (the picks' ``take_along_axis``, the loads' ``bincount``), a
    scatter of ``T * E`` elements under any scope or none (the picks'
    transpose, which carried none), a gather of ``s32[n * k]`` under
    ``moe_dispatch`` (``dst[rank]``: a part of the tokens plans ``T / chunks``
    of them under a ``while``, so any length is held against)."""
    return [op for op in moe_rows_micro.index_ops(text) if (
        op["scope"] == "moe_router" and op["op"] != "sort"
        or op["op"] == "scatter" and op["result"].endswith("[%d]" % (T * E))
        or op["op"] == "gather" and op["scope"] == "moe_dispatch"
        and op["result"] in {"s32[%d]" % (T * k // chunks)
                             for chunks in (1, 2, 4, 8)})]


@pytest.mark.parametrize("text_of,E,k", [
    pytest.param(_trinity_remat_text, 128, 8, id="trinity"),
    pytest.param(_smallthinker_routine_text, 64, 6, id="smallthinker")])
def test_router_and_plan_index_nothing_a_scalar_at_a_time(one_chip, as_tpu,
                                                         text_of, E, k):
    """Trinity's layer under the block's remat (the sigmoid-and-bias router)
    and SmallThinker's router with its routine (the softmax one): the router's
    scope holds its ``top_k``'s sort and no gather and no scatter, no scatter
    of ``T * E`` scalars is left under any scope or none, and a plan is two
    sorts of the ``T * k`` pairs (the second carries each pair's padded row as
    its payload, two arrays as the first) with no gather of ``s32[T * k]``
    behind them. What is left a scalar at a time are the plans' ``row_pair``
    and the combine's transposes, over tables with no small axis."""
    from collections import Counter

    text = text_of(one_chip)
    assert _scalar_index_ops(text, 8192, E, k) == []
    ops = Counter((op["scope"], op["op"], op["result"], op["arrays"])
                  for op in moe_rows_micro.index_ops(text) if not op["loop"]
                  and (op["op"] == "sort" or op["scope"] == "moe_router"))
    assert ops == {
        ("moe_router", "sort", "f32[8192,%d]" % E, 2): 1,
        ("moe_dispatch", "sort", "s32[%d]" % (8192 * k), 2): 2}, ops
    # in the parts (the ``while``) too: a part's plan of its own rows counts
    # them by comparison, with no scatter-add into ``held + 1`` bins
    assert not [op for op in moe_rows_micro.index_ops(text)
                if op["op"] == "scatter"]


def test_index_ops_reads_a_text_with_the_forms_this_file_left():
    """``moe_rows_micro.index_ops`` on lines of the kinds a compiled text
    holds: a scalar gather inside a fusion that carries the scope, a scatter
    whose fusion carries none, a sort under a ``while``."""
    text = """%fused_computation.1 (p0: f32[8192,128], p1: s32[65536,2]) -> f32[65536] {
  %p0 = f32[8192,128]{1,0} parameter(0)
  %p1 = s32[65536,2]{1,0} parameter(1)
  ROOT %gather.1 = f32[65536]{0:T(1024)} gather(%p0, %p1), offset_dims={}, collapsed_slice_dims={0,1}, start_index_map={0,1}, index_vector_dim=1, slice_sizes={1,1}
}

%fused_computation.2 (p0: f32[1048576], p1: s32[8192,8,1], p2: f32[8192,8]) -> f32[1048576] {
  %p0 = f32[1048576]{0} parameter(0)
  %p1 = s32[8192,8,1]{2,1,0} parameter(1)
  %p2 = f32[8192,8]{1,0} parameter(2)
  ROOT %scatter.6 = f32[1048576]{0:T(1024)S(1)} scatter(%p0, %p1, %p2), update_window_dims={}, inserted_window_dims={0}, scatter_dims_to_operand_dims={0}, index_vector_dim=2, to_apply=%region_58.158
}

ENTRY %main (a: f32[8192,128], b: s32[65536,2]) -> f32[65536] {
  %a = f32[8192,128]{1,0} parameter(0)
  %fusion.51 = f32[65536]{0:T(1024)S(1)} fusion(%a, %b), kind=kCustom, calls=%fused_computation.1, metadata={op_name="jit(f)/Block/moe/moe_router/jit(take_along_axis)/gather" stack_frame_id=4}
  %fusion.52 = f32[1048576]{0:T(1024)} fusion(%z, %i, %u), kind=kCustom, calls=%fused_computation.2
  %sort.153 = (s32[16384]{0:T(1024)}, s32[16384]{0:T(1024)S(1)}) sort(%keys, %iota.263), dimensions={0}, is_stable=true, to_apply=%region_33.93, metadata={op_name="jit(f)/Block/moe/cond/branch_0_fun/while/body/closed_call/moe_dispatch/sort" stack_frame_id=17}
  %gather.9 = bf16[8192,2048]{1,0:T(8,128)(2,1)} gather(%x_pad, %rows), offset_dims={1}, collapsed_slice_dims={0}, start_index_map={0}, index_vector_dim=1, slice_sizes={1,2048}, metadata={op_name="jit(f)/Block/mlp/take" stack_frame_id=9}
}
"""
    assert moe_rows_micro.index_ops(text) == [
        dict(op="gather", result="f32[65536]", indices=65536, each=1,
             arrays=1, scope="moe_router", loop=False),
        dict(op="scatter", result="f32[1048576]", indices=65536, each=1,
             arrays=1, scope=None, loop=False),
        dict(op="sort", result="s32[16384]", indices=16384, each=1, arrays=2,
             scope="moe_dispatch", loop=True),
        dict(op="gather", result="bf16[8192,2048]", indices=8192, each=2048,
             arrays=1, scope="other", loop=False)]


@pytest.mark.parametrize("d,P,parts", [(2048, 18432, 1), (2560, 26624, 2),
                                       (2560, 22912, 1), (2560, 23040, 2)])
def test_choice_sum_source_is_placed_in_vmem(one_chip, d, P, parts):
    """The combine alone at each expert cell's padded rows and on each side of
    the rule's edge (112 MiB: 22,936 rows of 2560 in bf16), its source made by
    an operation as in the step: every slab is gathered from a source that the
    compiler placed in VMEM (``S(1)``), which is what the column parts are
    for; asked for whole, the 136 MB source is gathered from HBM."""
    from pytorch_distributed_training_example_tpu.parallel import moe as moe_lib

    args = (_sds((P, d), one_chip), _sds((P, d), one_chip),
            _sds((6, 8192), one_chip, jnp.int32),
            _sds((8192, 6), one_chip, jnp.float32))
    sources = lambda fn: [
        source for _, _, source in moe_rows_micro.row_gathers(
            jax.jit(fn).lower(*args).compile().as_text())]
    found = sources(lambda a, b, pair_row, w: moe_lib._choice_sum(
        a + b, pair_row, w))
    assert len(found) == 6 * parts, found
    assert all(source.startswith("bf16[%d,%d]{" % (P, d // parts))
               and "S(1)" in source for source in found), found
    if parts > 1:
        whole = sources(lambda a, b, pair_row, w: moe_lib._choice_sum_in(
            a + b, pair_row, w, 1))
        assert len(whole) == 6 and not any("S(1)" in s for s in whole), whole


@pytest.mark.slow  # 50 s of the TPU compiler on every core, as Trinity's
def test_smallthinker_share_step_fits_the_chip(one_chip, as_tpu):
    """The benchmark cell's step (``smallthinker_21b_share`` at 1 x 8192,
    bf16, per-block remat, AdamW) compiles for a described v5e under the
    chip's memory: 13.23 GB (PERF.md has the chip's own reading); every flash
    plan takes 28 query heads to 4, and under the blocks' remat each layer's
    attention forward stays one call."""
    import re
    from collections import Counter

    compiled, mem, held = _share_step("smallthinker_21b_share", one_chip)
    assert mem.argument_size_in_bytes == pytest.approx(656_529_920 * 12,
                                                       rel=1e-3)
    assert held < 16.0e9, held
    text = compiled.as_text()
    assert _scalar_index_ops(text, 8192, 64, 6) == []
    calls = Counter(m.group(1) for m in re.finditer(
        r"%([a-z_]+)[.\d]* = [^\n]*tpu_custom_call", text))
    for name in ("flash_fwd_window", "flash_bwd_window_dq",
                 "flash_bwd_window_dkv"):
        assert calls[name] == 3, calls       # three window layers
    for name in ("flash_fwd_online", "flash_bwd_dq", "flash_bwd_dkv"):
        assert calls[name] == 1, calls       # one full layer
    # four layers, both sides of the ``cond``: four forwards a layer (each
    # side's own and its recomputation, all of them the rule's forward that
    # keeps ``gate`` and ``up``; nothing in the block reads the expert
    # layer's output, so the whole side's recomputation runs no down
    # projection), the down projection again in the whole side's backward,
    # the four transposes on both
    assert calls["gated_ffn_up"] == 4 * 4, calls
    assert calls["gated_ffn_down"] == 4 * 4, calls
    assert all(calls[name] == 4 * 2 for name in _GATED_BACKWARD), calls
    assert not calls["grouped_matmul"] + calls["grouped_matmul_dw"], calls
    # two big moves a layer (the combine and the dispatch's transpose: nothing
    # in the block reads the expert layer's output, so the remat's combine is
    # dropped), six slabs a column part, each from a 68 MB part in VMEM; no
    # gather reads the 136 MB ``[26624, 2560]`` whole
    gathers = [(rows, source) for scope, rows, source in
               moe_rows_micro.row_gathers(text) if "/moe/" in scope]
    assert not [g for g in gathers if g[1].startswith("bf16[26624,2560]")]
    slabs = [source for rows, source in gathers if rows == "bf16[8192,1280]"]
    assert len(slabs) == 4 * 2 * 2 * 6, len(slabs)
    assert all(source.startswith("bf16[26624,1280]{") and "S(1)" in source
               for source in slabs), set(slabs)


# -- GLM-4.7-Flash's share (models/glm_moe_lite.py): latent attention at 20
# -- heads of 256 in six blocks, a second depth's head, and the whole step


@pytest.mark.slow  # 80 s of the TPU compiler on every core, as Trinity's
def test_glm47_share_step_fits_the_chip(one_chip, as_tpu):
    """The benchmark cell's step (``glm47_flash_share`` at 1 x 8192, bf16,
    per-block remat, AdamW) compiles for a described v5e under the chip's
    memory: 15.56 GB (PERF.md has the chip's own reading); six blocks' latent
    attention is six calls of each online kernel (under the blocks' remat
    each forward stays one call), and the five expert layers' grouped
    matmuls."""
    import re
    from collections import Counter

    compiled, mem, held = _share_step("glm47_flash_share", one_chip)
    assert mem.argument_size_in_bytes == pytest.approx(706_518_528 * 12,
                                                       rel=1e-3)
    assert held < 16.0e9, held
    text = compiled.as_text()
    assert _scalar_index_ops(text, 8192, 64, 4) == []
    calls = Counter(m.group(1) for m in re.finditer(
        r"%([a-z_]+)[.\d]* = [^\n]*tpu_custom_call", text))
    for name in ("flash_fwd_online", "flash_bwd_dq", "flash_bwd_dkv"):
        assert calls[name] == 6, calls    # dense, four expert, the module's
    assert calls["gated_ffn_up"] == 5 * 4, calls      # as SmallThinker's
    assert calls["gated_ffn_down"] == 5 * 4, calls
    assert all(calls[name] == 5 * 2 for name in _GATED_BACKWARD), calls
    assert not calls["grouped_matmul"] + calls["grouped_matmul_dw"], calls
    assert not [name for name in calls if name.startswith("flash_")
                and name not in ("flash_fwd_online", "flash_bwd_dq",
                                 "flash_bwd_dkv")], calls
    for scope in ("mla_q", "mla_kv", "mla_rope", "mla_out", "mtp_merge",
                  "mtp/head_loss"):
        assert f"/{scope}/" in text, scope


@pytest.mark.parametrize("B,S,H,D", [
    (1, 8192, 20, 256),    # GLM-4.7-Flash's six attentions
    (1, 8192, 32, 128),    # Trinity's full layer
    (1, 4096, 32, 128),    # a 4 x 4 rectangle: 6 blocks never a step
])
def test_online_causal_schedule_compiles_at_the_cells_widths(one_chip, B, S,
                                                             H, D):
    """The three online kernels under the causal schedule (a walked table of
    steps through scalar prefetch, an unmasked body, a body of sub-tiles for
    each offset of a crossed block) compile for the v5e at the blocks dispatch
    picks, and keep the names the benchmark's readers find them by."""
    import re

    x = _sds((B, S, H, D), one_chip)
    text = _compiled_text(_grads(lambda q, k, v: fa.flash_attention(
        q, k, v, True, fa.DEFAULT_BLOCK_Q, fa.DEFAULT_BLOCK_KV, "online")),
        x, x, x)
    # "%transpose_jvp_flash_bwd_dq__.1 = bf16[...] custom-call(...)"
    calls = re.findall(r"%(\w*flash_\w+?)_*(?:\.\d+)? = [^\n]*custom-call",
                       text)
    assert sorted(c[c.index("flash_"):] for c in calls) == sorted(
        fa.ONLINE_KERNELS)
    blocks = [fa._online_blocks(bwd, S, D, 1024, 1024) for bwd in (0, 1, 1)]
    plans = [fa.online_schedule(name, True, S, S, *b)
             for name, b in zip(fa.ONLINE_KERNELS, blocks)]
    assert all(p.walk and p.split and p.sub == 512 for p in plans)
    assert blocks[1] == ((512, 1024) if D == 256 else (1024, 1024))


# -- Nemotron-3-Nano's share (models/nemotron_h.py): the scan's kernels at 8
# -- B/C groups and chunk 128, grouped matmuls at an expert width of 14.5 lane
# -- tiles, ungated experts behind the sigmoid router, GQA 32/2, the whole step


@pytest.mark.parametrize("dtype,heads", [(BF16, 32), (jnp.float32, 16)],
                         ids=["bf16", "fp32"])
def test_ssd_kernels_compile_at_nemotron_widths(one_chip, as_tpu, dtype,
                                                heads):
    """H64 P64 N128 with 8 B/C groups at chunk 128 over one 8,192 sequence:
    the kernel pair serves it, forward and all six cotangents, at a program of
    four whole groups in bf16 and of two in float32; the [64, 64, 128, 128]
    float32 tiles of the ``jax.numpy`` scan are nowhere."""
    from pytorch_distributed_training_example_tpu.ops import ssd

    S, H, Pd, N, G = 8192, 64, 64, 128, 8
    assert ssd._kernel_plan(H, Pd, N, 128, dtype, G) == heads
    f32 = jnp.float32
    args = (_sds((1, S, H, Pd), one_chip, dtype), _sds((1, S, H), one_chip, f32),
            _sds((H,), one_chip, f32), _sds((1, S, G, N), one_chip, dtype),
            _sds((1, S, G, N), one_chip, dtype), _sds((H,), one_chip, f32))
    total = lambda *a: ssd.ssd(*a, chunk=128).astype(f32).sum()
    compiled = jax.jit(jax.grad(total, argnums=(0, 1, 2, 3, 4, 5))).lower(
        *args).compile()
    text = compiled.as_text()
    assert "ssd_fwd" in text and "ssd_bwd" in text
    assert "64,128,128]" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 29


def test_grouped_ffn_compiles_at_a_width_off_the_lane_tiling(one_chip,
                                                             as_tpu):
    """d 2688 (21 lane tiles) and f 1856 (14.5): every one of the six grouped
    products lowers, in column blocks of whole lane tiles whose last one is
    part-filled (this call failed in Mosaic's block check before PR 43)."""
    T, E, d, ffn = 3072, 8, 2688, 1856
    assert grouped_matmul._block_cols(ffn, d, 2) == 640    # 3 blocks: 5 + 5 + 4.5
    assert grouped_matmul._block_cols(ffn, d, 4) == 384
    assert grouped_matmul._block_cols(d, ffn, 2) == 896    # 3 x 7 tiles
    seg = _sds((E,), one_chip, jnp.int32)

    def grads(x, w_up, w_down, starts, counts):
        return _grads(lambda x, wu, wd: grouped_matmul.grouped_ffn(
            x, wu, wd, starts, counts))(x, w_up, w_down)

    text = _compiled_text(grads, _sds((T, d), one_chip),
                          _sds((E, d, ffn), one_chip),
                          _sds((E, ffn, d), one_chip), seg, seg)
    assert "grouped_matmul_dw" in text


def _nemotron_layer(one_chip):
    """Nemotron-3-Nano's expert layer as shapes on the chip: 8 held of 128,
    6 a token, 8,192 tokens of 2688, ungated experts of 1856 beside a shared
    one of 3712."""
    from pytorch_distributed_training_example_tpu.parallel import moe as moe_lib

    layer = moe_lib.SharedExpertMoE(
        num_experts=128, ffn_dim=1856, top_k=6, held_experts=(8, 0),
        shared_ffn_dim=3712, route_scale=2.5, balance_coeff=0.001,
        gated=False, dtype=BF16, param_dtype=jnp.float32)
    x = jnp.zeros((1, 8192, 2688), BF16)
    shapes = jax.eval_shape(lambda: layer.init(jax.random.key(0), x,
                                               train=False))
    on_chip = lambda tree: jax.tree.map(
        lambda s: _sds(s.shape, one_chip, s.dtype), tree)
    return (layer, on_chip(shapes["params"]), on_chip(shapes["batch_stats"]),
            _sds(x.shape, one_chip))


def test_ungated_held_experts_run_the_routed_forward_twice(one_chip, as_tpu):
    """The ungated layer under the block's remat, with a consumer that needs
    its output: the leaves keep the published shapes (no padded copy of a
    weight), and on the whole layout's side of the ``cond``s the step holds
    the forward's two grouped matmuls, the recomputation's two, and of the
    backward the down projection again, two ``dx`` and two ``dw``: the routed
    forward runs twice a step, not three times; ``up`` crosses the ``cond``
    in the compute dtype."""
    import re
    from collections import Counter

    layer, params, stats, x = _nemotron_layer(one_chip)
    assert params["w_up"].shape == (8, 2688, 1856)
    assert params["w_down"].shape == (8, 1856, 2688)
    assert "w_gate" not in params and set(params["shared"]) == {"up", "down"}

    def grads(params, stats, x):
        block = jax.checkpoint(
            lambda p, x: jnp.sin(layer.apply(
                {"params": p, "batch_stats": stats}, x, train=False).astype(
                    jnp.float32)),
            prevent_cse=False,
            policy=jax.checkpoint_policies.nothing_saveable)
        return jax.grad(lambda p, x: block(p, x).sum(), argnums=(0, 1))(
            params, x)

    text = _compiled_text(grads, params, stats, x)
    found = [(m.group(1), m.group(2)) for m in re.finditer(
        r"%(grouped_matmul(?:_dw)?)[.\d]* = [^\n]*tpu_custom_call"
        r"[^\n]*op_name=\"([^\"]*)\"", text) if "/while/" not in m.group(2)]
    calls = Counter(name for name, _ in found)
    # (2 + 2 + 3; XLA may fold the recomputation into the forward it equals)
    assert 0 < calls["grouped_matmul"] <= 7, calls
    assert calls["grouped_matmul_dw"] == 2, calls
    backward = Counter(name for name, scope in found if "transpose(" in scope
                       and "rematted_computation" not in scope)
    assert backward == {"grouped_matmul": 3, "grouped_matmul_dw": 2}, backward
    # the bounded layout: 8,192 x 6 pairs in a 16th of... 8 parts: 56 tiles
    crossing = re.findall(r" = (\(.*?\)) conditional\(", text)
    assert crossing and any("bf16[7168,1856]" in c for c in crossing)
    assert not any("f32[7168,1856]" in c for c in crossing)
    assert "[8,2688,1920]" not in text and "[8,2688,2048]" not in text
    for scope in ("moe_router", "moe_dispatch", "moe_experts", "moe_combine",
                  "moe_shared"):
        assert f"/{scope}/" in text, scope


def test_flash_compiles_at_nemotron_widths(one_chip):
    """B1 S8192 H32/2 D128, causal, no window: the three online kernels, the
    keys' and values' gradients folded back from 32 heads to 2 outside them."""
    q = _sds((1, 8192, 32, 128), one_chip)
    kv = _sds((1, 8192, 2, 128), one_chip)
    text = _compiled_text(_grads(lambda q, k, v: fa.flash_attention(
        q, k, v, True)), q, kv, kv)
    for name in fa.ONLINE_KERNELS:
        assert name in text, name
    assert "flash_fwd_window" not in text


@pytest.mark.slow  # the TPU compiler on every core for minutes, as Trinity's
def test_nemotron_share_step_fits_the_chip(one_chip, as_tpu):
    """The benchmark cell's step (``nemotron3_nano_share`` at 1 x 8192, bf16,
    per-block remat, AdamW) compiles for a described v5e under the chip's
    memory, with the scan's kernels and the mixer's two stages' four times
    each way, the online flash kernels once and the four expert layers'
    grouped matmuls."""
    import re
    from collections import Counter

    compiled, mem, held = _share_step("nemotron3_nano_share", one_chip)
    assert mem.argument_size_in_bytes == pytest.approx(666_962_944 * 12,
                                                       rel=1e-3)
    assert held < 16.0e9, held
    text = compiled.as_text()
    assert _scalar_index_ops(text, 8192, 128, 6) == []
    calls = Counter(m.group(1) for m in re.finditer(
        r"%([a-z_]+)[.\d]* = [^\n]*tpu_custom_call", text))
    assert calls["ssd_bwd"] == 4 and calls["ssd_fwd"] >= 4, calls
    # the mixers' elementwise stages as kernels (a call sits in the fusion
    # that holds the lane slice it reads), and with them no float32 copy of
    # xBC nor its padded cotangents: 7.36 GB of temporaries before them
    for stage in ("conv_silu", "gate_norm"):
        assert calls[stage + "_bwd"] == 4 and calls[stage + "_fwd"] >= 4, calls
    assert mem.temp_size_in_bytes < 6.5e9, mem.temp_size_in_bytes
    for name in fa.ONLINE_KERNELS:
        assert calls[name] == 1, calls
    # four layers' two matrices, on both sides of the bounded layout's cond
    assert calls["grouped_matmul"] and calls["grouped_matmul_dw"] == 4 * 2 * 2


# -- LFM2-8B-A1B's share (models/lfm2_moe.py): causal GQA 32/8 at a head of 64
# -- over 8,192 rotated positions, the gated conv as XLA's fusions, the step


def test_flash_compiles_at_lfm2_widths(one_chip):
    """B1 S8192 H32/8 D64, causal, no window: no causal, one-shot or
    streaming plan reaches S=8192 at this width, so the three online kernels
    at 1024 x 1024 blocks both ways, under the causal schedule (a walked
    table, sub-tiles on the crossed blocks)."""
    q = _sds((1, 8192, 32, 64), one_chip)
    kv = _sds((1, 8192, 8, 64), one_chip)
    text = _compiled_text(_grads(lambda q, k, v: fa.flash_attention(
        q, k, v, True)), q, kv, kv)
    for name in fa.ONLINE_KERNELS:
        assert name in text, name
    for other in ("flash_fwd_window", "flash_fwd_causal", "flash_bwd_causal",
                  "flash_bwd_oneshot"):
        assert other not in text, other
    assert [fa._online_blocks(bwd, 8192, 64, 1024, 1024, 2)
            for bwd in (False, True)] == [(1024, 1024), (1024, 1024)]
    plans = [fa.online_schedule(name, True, 8192, 8192, 1024, 1024)
             for name in fa.ONLINE_KERNELS]
    assert all(p.walk and p.split for p in plans)


@pytest.mark.slow  # the TPU compiler on every core for minutes, as Trinity's
def test_lfm2_share_step_fits_the_chip(one_chip, as_tpu):
    """The benchmark cell's step (``lfm2_8b_a1b_share`` at 1 x 8192, bf16,
    per-block remat, AdamW) compiles for a described v5e under the chip's
    memory: seven layers (five gated convs, two attentions, six expert FFNs);
    the online flash kernels twice each, the six expert layers' grouped
    matmuls, and no Pallas call under ``conv_gate``: the gated conv is XLA's
    own fusions in this PR."""
    import re
    from collections import Counter

    compiled, mem, held = _share_step("lfm2_8b_a1b_share", one_chip)
    assert mem.argument_size_in_bytes == pytest.approx(711_389_440 * 12,
                                                       rel=1e-3)
    assert held < 16.0e9, held
    text = compiled.as_text()
    assert _scalar_index_ops(text, 8192, 32, 4) == []
    calls = Counter(m.group(1) for m in re.finditer(
        r"%([a-z_]+)[.\d]* = [^\n]*tpu_custom_call", text))
    for name in fa.ONLINE_KERNELS:
        assert calls[name] == 2, calls
    assert calls["gated_ffn_up"] == 6 * 4, calls      # as SmallThinker's
    assert calls["gated_ffn_down"] == 6 * 4, calls
    assert all(calls[name] == 6 * 2 for name in _GATED_BACKWARD), calls
    assert not calls["grouped_matmul"] + calls["grouped_matmul_dw"], calls
    assert not [line for line in text.splitlines()
                if "tpu_custom_call" in line and "conv_gate" in line]
    for scope in ("short_conv", "conv_gate", "in_proj", "out_proj",
                  "moe_router", "moe_experts"):
        assert f"/{scope}/" in text, scope


# -- Qwen3-Next-80B-A3B's share (models/qwen3_next.py): the chunked gated delta
# -- rule as XLA's scan, causal GQA 16/2 at a head of 256 over 8,192 positions
# -- of which a quarter of the head is rotated, the step


def test_flash_compiles_at_qwen3_next_widths(one_chip):
    """B1 S8192 H16/2 D256, causal, no window: eight query heads a key head at
    GLM's head width; no causal, one-shot or streaming plan reaches S=8192 at
    this width, so the three online kernels under the causal schedule."""
    q = _sds((1, 8192, 16, 256), one_chip)
    kv = _sds((1, 8192, 2, 256), one_chip)
    text = _compiled_text(_grads(lambda q, k, v: fa.flash_attention(
        q, k, v, True)), q, kv, kv)
    for name in fa.ONLINE_KERNELS:
        assert name in text, name
    for other in ("flash_fwd_window", "flash_fwd_causal", "flash_bwd_causal",
                  "flash_bwd_oneshot"):
        assert other not in text, other
    plans = [fa.online_schedule(name, True, 8192, 8192, *fa._online_blocks(
        name != "flash_fwd_online", 8192, 256, 1024, 1024, 2))
        for name in fa.ONLINE_KERNELS]
    assert all(p.walk and p.split for p in plans)


@pytest.mark.parametrize("dtype", [BF16, jnp.float32], ids=["bf16", "fp32"])
def test_gated_delta_rule_compiles_at_published_widths(one_chip, as_tpu,
                                                       dtype):
    """The chunked rule and its transpose at 1 x 8192, 16 key heads to 32
    value heads of 128, chunk 64: the kernel pair (eight key heads a program,
    so a grid of 1 x 2 x 128 chunks), one call each way, no ``while`` loop of
    XLA's and no triangular-solve call, and the states the chunks start from
    as the one large residual."""
    from pytorch_distributed_training_example_tpu.ops import gated_delta

    qk = _sds((1, 8192, 16, 128), one_chip, dtype)
    v = _sds((1, 8192, 32, 128), one_chip, dtype)
    gates = _sds((1, 8192, 32), one_chip, jnp.float32)
    rule = jax.grad(lambda *a: gated_delta.gated_delta_rule(*a).sum(),
                    argnums=(0, 1, 2, 3, 4))
    assert _pallas_calls(rule, qk, qk, v, gates, gates) == {
        "delta_rule_fwd": ((1, 2, 128), 0), "delta_rule_bwd": ((1, 2, 128), 0)}
    text = _compiled_text(rule, qk, qk, v, gates, gates)
    assert text.count("tpu_custom_call") == 2
    assert " while(" not in text and "triangular-solve" not in text
    assert "f32[1,128,32,128,128]" in text      # the states a chunk starts from


def test_gated_delta_rule_xla_body_compiles_at_narrow_heads(one_chip):
    """Heads that are no whole lane tile keep XLA's own program: no Pallas
    call, the chunks under ``while`` loops and not unrolled."""
    from pytorch_distributed_training_example_tpu.ops import gated_delta

    qk = _sds((1, 8192, 16, 64), one_chip)
    v = _sds((1, 8192, 32, 64), one_chip)
    gates = _sds((1, 8192, 32), one_chip, jnp.float32)
    text = jax.jit(jax.grad(
        lambda *a: gated_delta.gated_delta_rule(*a).sum(),
        argnums=(0, 1, 2, 3, 4))).lower(qk, qk, v, gates,
                                        gates).compile().as_text()
    assert "tpu_custom_call" not in text
    assert 2 <= text.count(" while(") <= 4, text.count(" while(")
    assert "triangular-solve" not in text


@pytest.mark.slow  # two minutes of the TPU compiler on every core, as Trinity's
def test_qwen3_next_share_step_fits_the_chip(one_chip, as_tpu):
    """The benchmark cell's step (``qwen3_next_80b_share`` at 1 x 8192, bf16,
    per-block remat, AdamW) compiles for a described v5e under the chip's
    memory: 14.29 GB, under the 15.10 it took while the head norm and the gate
    were ``jax.numpy`` on a 4-D view; four layers: three conv kernels each
    way, the delta rule's pair and the ``norm_gate`` pair as often (a block's
    recomputed forward is merged with the step's own where the inputs are the
    same values, as the conv's is) with no float32 ``copy`` of a ``[..., 32,
    128]`` shape left between them, every Pallas call under ``delta_rule`` one
    of the pair and no ``while`` left there, the online flash kernels once
    each, the four expert layers' gated-FFN kernels, and nothing in the router
    or the plan at E = 512, k = 10 that indexes a scalar at a time."""
    import re
    from collections import Counter

    compiled, mem, held = _share_step("qwen3_next_80b_share", one_chip)
    assert mem.argument_size_in_bytes == pytest.approx(625_667_136 * 12,
                                                       rel=1e-3)
    assert held <= 14.4e9, held
    text = compiled.as_text()
    assert not re.findall(r"= f32\[[\d,]*32,128\]\S* copy\(", text)
    assert _scalar_index_ops(text, 8192, 512, 10) == []
    calls = Counter(m.group(1) for m in re.finditer(
        r"%([a-z_]+)[.\d]* = [^\n]*tpu_custom_call", text))
    for name in fa.ONLINE_KERNELS:
        assert calls[name] == 1, calls
    assert calls["conv_silu_fwd"] == calls["conv_silu_bwd"] == 3, calls
    assert calls["gated_ffn_up"] == 4 * 4, calls      # as SmallThinker's
    assert calls["gated_ffn_down"] == 4 * 4, calls
    assert all(calls[name] == 4 * 2 for name in _GATED_BACKWARD), calls
    assert not calls["grouped_matmul"] + calls["grouped_matmul_dw"], calls
    assert calls["delta_rule_fwd"] == calls["delta_rule_bwd"] == 3, calls
    assert calls["norm_gate_fwd"] == calls["norm_gate_bwd"] == 3, calls
    under_rule = [line for line in text.splitlines() if "/delta_rule/" in line]
    assert sum("tpu_custom_call" in line for line in under_rule) == 6
    assert not [line for line in under_rule if " while(" in line]
    for scope in ("gated_delta_net", "delta_rule", "conv_silu", "gate_norm",
                  "in_proj", "out_proj", "moe_router", "moe_experts",
                  "moe_shared"):
        assert f"/{scope}/" in text, scope


# -- Xing4.0's share (models/xing4.py): the online kernels at a value width
# -- under the query/key width, grouped matmuls at 3584 x 1024, the whole step
# -- with its four-wide float32 stream


@pytest.mark.parametrize("B,S,H,qk,v", [
    (1, 2048, 32, 192, 128),   # the Xing4.0 cell's five attentions
    (1, 1024, 4, 64, 128),     # a value width over the query/key width
])
def test_online_kernels_compile_at_unequal_widths(one_chip, B, S, H, qk, v):
    """q and k ``qk`` wide, v and the result ``v`` wide, with a softmax scale
    of the caller's: the three online kernels under the causal schedule, each
    operand blocked at its own width (no operand of the call is padded to the
    other's: the step has no ``pad``, and the calls' results have the widths
    their cotangents came with), at the blocks the plan gives the pair."""
    import re

    wide = _sds((B, S, H, qk), one_chip)
    narrow = _sds((B, S, H, v), one_chip)
    text = _compiled_text(_grads(lambda q, k, v: fa.flash_attention(
        q, k, v, True, fa.DEFAULT_BLOCK_Q, fa.DEFAULT_BLOCK_KV, "auto", None,
        None, 0.1447)), wide, wide, narrow)
    calls = re.findall(r"%(\w*flash_\w+?)_*(?:\.\d+)? = ([^\n]*)custom-call"
                       r"\(([^\n]*?)\), custom_call_target", text)
    assert sorted(c[0][c[0].index("flash_"):] for c in calls) == sorted(
        fa.ONLINE_KERNELS)
    # o and dv at the value width, dq and dk at the query/key width, and no
    # operand widened on its way into a call
    results = sorted(int(w) for _, result, _ in calls for w in re.findall(
        rf"bf16\[{B},{H},{S},(\d+)\]", result))
    assert results == sorted([v, qk, qk, v]), calls
    assert " pad(" not in text
    assert [fa._online_blocks(bwd, S, (qk, v), 1024, 1024, 2)
            for bwd in (False, True)] == [(1024, 1024)] * 2
    assert fa._online_held(True, 1024, 1024, (192, 128), 2) == \
        2 * 2 * 3 * 1024 * 320 + 4 * 1024 * 320 <= fa.ONLINE_HELD_MAX
    with pytest.raises(ValueError, match="one head width"):
        fa.flash_attention(wide, wide, narrow, True, 1024, 1024, "oneshot")


def test_grouped_ffn_compiles_at_xing4_widths(one_chip, as_tpu):
    """Xing4.0's expert layer: 8 held experts of 64, 4 a token, 2,048 tokens
    of 3584 (28 lane tiles), experts of 1024, a shared expert of 1024;
    forward and backward through the gated kernels, the bounded layout's
    ``cond``. Neither width had run."""
    text = _layer_grads_text(*_held_experts_layer(
        one_chip, num_experts=64, ffn_dim=1024, top_k=4, held=8,
        route_scale=2.0, d=3584, tokens=2048))
    assert "gated_ffn_dw_up" in text and "conditional" in text
    calls = _expert_kernel_calls(text)
    assert {"gated_ffn_up", "gated_ffn_down", *_GATED_BACKWARD} <= {
        name for name, _ in calls}


def test_xing4_share_step_fits_the_chip(one_chip, as_tpu):
    """The benchmark cell's step (``xing4_29b_share`` at 1 x 2048, bf16,
    per-block remat, AdamW) compiles for a described v5e under the chip's
    memory (PERF.md has the chip's own reading): five blocks' latent
    attention is five calls of each online kernel at 192 / 128, the four
    expert layers' gated kernels, and every hyper-connection's scopes."""
    import re
    from collections import Counter

    compiled, mem, held = _share_step("xing4_29b_share", one_chip,
                                      seq_len=2048)
    assert mem.argument_size_in_bytes == pytest.approx(759_346_190 * 12,
                                                       rel=1e-3)
    assert 12.2e9 < held < 15.6e9, held
    text = compiled.as_text()
    assert _scalar_index_ops(text, 2048, 64, 4) == []
    calls = Counter(m.group(1) for m in re.finditer(
        r"%([a-z_]+)[.\d]* = [^\n]*tpu_custom_call", text))
    for name in ("flash_fwd_online", "flash_bwd_dq", "flash_bwd_dkv"):
        assert calls[name] == 5, calls
    assert calls["gated_ffn_up"] and calls["gated_ffn_down"], calls
    assert not [name for name in calls if name.startswith("flash_")
                and name not in fa.ONLINE_KERNELS], calls
    assert "bf16[1,32,2048,192]" in text and "bf16[1,32,2048,128]" in text
    for scope in ("hc_maps", "hc_sinkhorn", "hc_read", "hc_write", "mla_q",
                  "mla_rope", "moe_router"):
        assert f"/{scope}/" in text, scope
