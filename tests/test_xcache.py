"""Persistent executable cache (core/xcache.py): fingerprint discipline,
save/load round trip, and corruption quarantine."""

import json
import logging
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_distributed_training_example_tpu.core import xcache


@pytest.fixture(autouse=True)
def _pdtx_reaches_caplog():
    """Trainer tests earlier in the suite run setup_logging(), which sets
    propagate=False on 'pdtx' — caplog's root handler would miss every
    MISS/HIT record here. Restore propagation for this module."""
    log = logging.getLogger("pdtx")
    prev = log.propagate
    log.propagate = True
    yield
    log.propagate = prev


def _mesh():
    return jax.sharding.Mesh(
        np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))


def _cfg(**over):
    base = {"model": "llama_tiny", "seq_len": 32, "global_batch_size": 8,
            "grad_accum_steps": 1, "precision": "fp32", "strategy": "dp",
            "optimizer": "adamw", "remat": False}
    base.update(over)
    return types.SimpleNamespace(**base)


def test_skeleton_roundtrip_and_rejects_fancy_containers():
    tree = {"loss": 1.0, "aux": ({"acc": 2.0}, [3.0, {"lr": 4.0}])}
    skel = xcache._skeleton(tree)
    json.dumps(skel)  # must be JSON-able: it is stored in meta.json
    rebuilt = xcache._unskeleton(skel)
    # Same treedef, leaves reset to placeholder floats.
    assert (jax.tree_util.tree_structure(rebuilt)
            == jax.tree_util.tree_structure(tree))
    assert jax.tree_util.tree_leaves(rebuilt) == [0.0] * 4
    with pytest.raises(TypeError):
        xcache._skeleton({1: "non-string key"})


def test_fingerprint_key_stable_and_knob_sensitive():
    mesh = _mesh()
    x = jnp.ones((4, 2), jnp.float32)
    key = xcache.cache_key(
        xcache.fingerprint(mesh=mesh, config=_cfg(), example_args=(x,)))
    again = xcache.cache_key(
        xcache.fingerprint(mesh=mesh, config=_cfg(), example_args=(x,)))
    assert key == again  # deterministic across calls

    # Every traced knob, shape change, or extra tag must move the key — a
    # stale hit is silent wrong math.
    for fields in (
            xcache.fingerprint(mesh=mesh, config=_cfg(grad_accum_steps=2),
                               example_args=(x,)),
            xcache.fingerprint(mesh=mesh, config=_cfg(precision="bf16"),
                               example_args=(x,)),
            xcache.fingerprint(mesh=mesh, config=_cfg(),
                               example_args=(jnp.ones((8, 2), jnp.float32),)),
            xcache.fingerprint(mesh=mesh, config=_cfg(), example_args=(x,),
                               extra={"phase": "serve"}),
    ):
        assert xcache.cache_key(fields) != key

    # Untraced attributes must NOT invalidate (no spurious cold compiles).
    cfg = _cfg()
    cfg.checkpoint_every_steps = 1234
    assert xcache.cache_key(xcache.fingerprint(
        mesh=mesh, config=cfg, example_args=(x,))) == key


def _another(value):
    """A value of ``value``'s type that is not ``value``."""
    if isinstance(value, bool):
        return not value
    if isinstance(value, (int, float)):
        return value + 1
    return f"{value}_other"


@pytest.mark.parametrize("knob", xcache.TRACED_KNOBS)
def test_each_traced_knob_alone_moves_the_key(knob):
    """Every entry of ``TRACED_KNOBS`` is a ``Config`` field, and changing
    that field alone changes ``cache_key``: an entry that named no field
    would key nothing, in silence (``fingerprint`` skips what the config
    lacks)."""
    from pytorch_distributed_training_example_tpu.utils.config import Config

    base = Config()
    changed = base.replace(**{knob: _another(getattr(base, knob))})
    key = lambda cfg: xcache.cache_key(
        xcache.fingerprint(mesh=_mesh(), config=cfg))
    assert key(changed) != key(base)
    assert key(base.replace()) == key(base)


def test_every_field_model_options_reads_is_a_traced_knob():
    """``Config.model_options()`` says which fields shape the model; each of
    them reaches tracing, so each must be in ``TRACED_KNOBS`` (by hand, until
    the tuple is derived: ROADMAP C5). And what it hands out is the
    registry's whole set of options, no more."""
    from pytorch_distributed_training_example_tpu.models import registry
    from pytorch_distributed_training_example_tpu.utils.config import Config

    cfg, read = Config(), set()

    class Reads:
        def __getattr__(self, name):
            read.add(name)
            return getattr(cfg, name)

    options = Config.model_options(Reads())
    assert read and read <= set(xcache.TRACED_KNOBS), (
        read - set(xcache.TRACED_KNOBS))
    assert set(options) == set(registry._OPTIONS)


def test_save_load_roundtrip_executes_warm(tmp_path, caplog):
    x = jnp.arange(4, dtype=jnp.float32)
    compiled = jax.jit(lambda v: v * 2.0 + 1.0).lower(x).compile()
    fields = xcache.fingerprint(mesh=_mesh(), example_args=(x,))

    with caplog.at_level("WARNING", logger="pdtx"):
        assert xcache.load(str(tmp_path), fields) is None  # empty cache
    assert any("MISS" in r.message for r in caplog.records)

    if not xcache.save(str(tmp_path), fields, compiled):
        pytest.skip("executable serialization unsupported on this backend")
    caplog.clear()
    with caplog.at_level("WARNING", logger="pdtx"):
        warm = xcache.load(str(tmp_path), fields)
    assert warm is not None
    assert any("HIT" in r.message for r in caplog.records)
    np.testing.assert_allclose(np.asarray(warm(x)),
                               np.asarray(x) * 2.0 + 1.0)


def test_load_quarantines_crc_corruption_and_recovers(tmp_path, caplog):
    x = jnp.arange(3, dtype=jnp.float32)
    compiled = jax.jit(lambda v: v - 1.0).lower(x).compile()
    fields = xcache.fingerprint(mesh=_mesh(), example_args=(x,))
    if not xcache.save(str(tmp_path), fields, compiled):
        pytest.skip("executable serialization unsupported on this backend")
    entry = os.path.join(xcache.cache_dir(str(tmp_path)),
                         xcache.cache_key(fields))
    with open(os.path.join(entry, xcache.EXECUTABLE_FILE), "r+b") as fh:
        fh.write(b"\xde\xad\xbe\xef")  # flip leading bytes

    with caplog.at_level("WARNING", logger="pdtx"):
        assert xcache.load(str(tmp_path), fields) is None
    assert any("CRC mismatch" in r.message for r in caplog.records)
    assert not os.path.isdir(entry)  # quarantined aside, never half-trusted
    assert os.path.isdir(entry + ".corrupt")

    # The recompile path re-saves under the same key and hits again.
    assert xcache.save(str(tmp_path), fields, compiled)
    assert xcache.load(str(tmp_path), fields) is not None


def test_load_refuses_fingerprint_mismatch_under_same_key(tmp_path, caplog):
    x = jnp.arange(3, dtype=jnp.float32)
    compiled = jax.jit(lambda v: v + 2.0).lower(x).compile()
    fields = xcache.fingerprint(mesh=_mesh(), example_args=(x,))
    if not xcache.save(str(tmp_path), fields, compiled):
        pytest.skip("executable serialization unsupported on this backend")
    entry = os.path.join(xcache.cache_dir(str(tmp_path)),
                         xcache.cache_key(fields))
    meta_path = os.path.join(entry, xcache.META_FILE)
    meta = json.load(open(meta_path))
    meta["fields"]["jax_version"] = "0.0.0-stale"
    with open(meta_path, "w") as fh:
        json.dump(meta, fh)

    with caplog.at_level("WARNING", logger="pdtx"):
        assert xcache.load(str(tmp_path), fields) is None
    assert any("fingerprint mismatch" in r.message
               and "stale" in r.message for r in caplog.records)
    assert os.path.isdir(entry)  # a mismatch is not corruption

    # Torn meta IS corruption: quarantine.
    with open(meta_path, "w") as fh:
        fh.write('{"crc32": 12')
    caplog.clear()
    with caplog.at_level("WARNING", logger="pdtx"):
        assert xcache.load(str(tmp_path), fields) is None
    assert os.path.isdir(entry + ".corrupt")


def test_reconstruct_mode_rebuilds_treedefs_from_live_example(
        tmp_path, monkeypatch):
    state = {"w": jnp.ones((2, 2), jnp.float32)}
    batch = {"x": jnp.full((2,), 3.0, jnp.float32)}

    def step(state, batch):
        new = {"w": state["w"] + 1.0}
        return new, {"loss": jnp.sum(batch["x"]), "aux": (jnp.float32(0.5),)}

    compiled = jax.jit(step).lower(state, batch).compile()
    metrics = jax.tree_util.tree_map(
        lambda a: a, step(state, batch)[1])  # same treedef as the output
    fields = xcache.fingerprint(mesh=_mesh(), example_args=(state, batch))

    # Force the trainer's real-world condition: treedefs that refuse to
    # pickle (the TrainState's optax closures), so save() must fall back
    # to reconstruct mode.
    def _no_pickle(_):
        raise TypeError("cannot pickle closure")

    monkeypatch.setattr(xcache.pickle, "dumps", _no_pickle)
    if not xcache.save(str(tmp_path), fields, compiled,
                       example=(state, batch), metrics=metrics):
        pytest.skip("executable serialization unsupported on this backend")
    entry = os.path.join(xcache.cache_dir(str(tmp_path)),
                         xcache.cache_key(fields))
    meta = json.load(open(os.path.join(entry, xcache.META_FILE)))
    assert meta["tree_mode"] == "reconstruct"
    monkeypatch.undo()

    # Without the live example the entry is unusable — loudly cold.
    assert xcache.load(str(tmp_path), fields) is None

    warm = xcache.load(str(tmp_path), fields, example=(state, batch))
    assert warm is not None
    new_state, out = warm(state, batch)
    np.testing.assert_allclose(np.asarray(new_state["w"]), 2.0)
    np.testing.assert_allclose(float(out["loss"]), 6.0)
    assert isinstance(out["aux"], tuple)  # treedef faithfully rebuilt


def test_compile_cached_modes(tmp_path):
    x = jnp.arange(5, dtype=jnp.float32)
    fields = xcache.fingerprint(mesh=_mesh(), example_args=(x,))
    lowered = jax.jit(lambda v: v * 3.0).lower(x)

    compiled, mode = xcache.compile_cached(lowered, None, fields)
    assert mode == "cold"  # no cache root: plain compile

    compiled, mode = xcache.compile_cached(lowered, str(tmp_path), fields)
    assert mode == "cold"
    if not os.path.isdir(os.path.join(xcache.cache_dir(str(tmp_path)),
                                      xcache.cache_key(fields))):
        pytest.skip("executable serialization unsupported on this backend")
    compiled, mode = xcache.compile_cached(lowered, str(tmp_path), fields)
    assert mode == "warm"
    np.testing.assert_allclose(np.asarray(compiled(x)), np.asarray(x) * 3.0)


# ---- compile-cache placement (the one helper every entry point uses) -----


@pytest.fixture
def _restore_cache_config():
    keep = (jax.config.jax_compilation_cache_dir,
            jax.config.jax_persistent_cache_min_compile_time_secs)
    yield
    jax.config.update("jax_compilation_cache_dir", keep[0])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", keep[1])


@pytest.mark.parametrize("env_set", [True, False])
def test_place_compile_cache_env_wins(monkeypatch, tmp_path,
                                      _restore_cache_config, env_set):
    """JAX_COMPILATION_CACHE_DIR set: no directory is set in code (the cache
    can be placed from outside). Unset: ONE fixed directory inside the
    checkout — never a temp name, a pid or the time."""
    before = jax.config.jax_compilation_cache_dir
    if env_set:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert xcache.place_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        got = xcache.place_compile_cache()
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert got == os.path.join(repo, ".jax_cache")
        assert got == xcache.DEFAULT_COMPILE_CACHE_DIR
        assert jax.config.jax_compilation_cache_dir == got


def test_only_the_helper_sets_the_cache_dir():
    """grep: ``jax_compilation_cache_dir`` is updated in core/xcache.py only."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    hits = []
    for root, dirs, files in os.walk(repo):
        dirs[:] = [d for d in dirs if not d.startswith((".", "_"))
                   and d not in ("chiprun_out", "tests")]
        for name in files:
            if name.endswith(".py"):
                path = os.path.join(root, name)
                with open(path) as fh:
                    if 'update("jax_compilation_cache_dir"' in fh.read():
                        hits.append(os.path.relpath(path, repo))
    assert hits == [os.path.join(
        "pytorch_distributed_training_example_tpu", "core", "xcache.py")]


def test_threefry_partitionable_is_the_default():
    """Sharding-invariant RNG (same init bits on any mesh) is the installed
    jax's default — nothing in main.py or conftest.py sets it any more."""
    assert jax.config.jax_threefry_partitionable is True
    key = jax.random.PRNGKey(0)
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = jax.sharding.Mesh(np.array(jax.devices()[:4]), ("x",))
    sharded = jax.jit(lambda k: jax.random.normal(k, (8, 16)),
                      out_shardings=NamedSharding(mesh, P("x")))(key)
    np.testing.assert_array_equal(
        np.asarray(sharded), np.asarray(jax.random.normal(key, (8, 16))))
