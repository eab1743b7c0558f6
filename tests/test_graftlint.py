"""graftlint: AST rules on synthetic fixtures, IR rules on tiny planted
programs, the whole-tree gate, and the check_regression --lint CLI."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmarks"))

import graftlint  # noqa: E402

PKG = graftlint.PKG
FIXTURE_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "fixtures")


def lint_tree(tmp_path, files):
    """Write {relpath: source} under tmp_path and run the AST layer."""
    for rel, src in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src))
    return graftlint.run_ast(str(tmp_path))


def rules(findings):
    return sorted(f.rule for f in findings)


# -- GL001: zero-copy snapshots escaping to threads -------------------------

def test_gl001_r11_fixture_flagged():
    """The historical corruption class must be caught, by rule ID."""
    found = graftlint.run_ast(FIXTURE_DIR, files=["r11_zero_copy_save.py"])
    assert [f.rule for f in found] == ["GL001"]
    assert "np.asarray" in found[0].message
    assert found[0].scope == "BrokenCheckpointer.save"


def test_gl001_fixed_shape_is_clean(tmp_path):
    """The post-r11 np.array copy must NOT be flagged."""
    src = (open(os.path.join(FIXTURE_DIR, "r11_zero_copy_save.py")).read()
           .replace("np.asarray(sh.data)", "np.array(sh.data)"))
    found = lint_tree(tmp_path, {"mod.py": src})
    assert rules(found) == []


def test_gl001_direct_assignment_into_closure(tmp_path):
    found = lint_tree(tmp_path, {"mod.py": """
        import threading
        import numpy as np

        def save(arrs):
            shards = {}
            for k, a in arrs.items():
                shards[k] = np.asarray(a.data)

            def write():
                for k, v in shards.items():
                    pass

            threading.Thread(target=write).start()
    """})
    assert rules(found) == ["GL001"]


def test_gl001_consumed_by_call_not_flagged(tmp_path):
    """str(np.asarray(x).dtype) stores no buffer; memoryview in a dict that
    never reaches a thread is fine too."""
    found = lint_tree(tmp_path, {"mod.py": """
        import threading
        import numpy as np

        def save(arrs):
            meta = {}
            local = {}
            for k, a in arrs.items():
                meta[k] = str(np.asarray(a).dtype)
                local[k] = np.asarray(a)  # never read by the thread

            def write():
                for k in meta:
                    pass

            threading.Thread(target=write).start()
    """})
    assert rules(found) == []


# -- GL002: fs ops bypassing retriable_io -----------------------------------

def test_gl002_bare_fs_op_flagged(tmp_path):
    found = lint_tree(tmp_path, {f"{PKG}/core/checkpoint.py": """
        import os

        def commit(path, step):
            with open(path, "w") as fh:
                fh.write(str(step))
            os.rename(path, path + ".done")
    """})
    assert rules(found) == ["GL002", "GL002"]


def test_gl002_wrapped_function_exempt(tmp_path):
    found = lint_tree(tmp_path, {f"{PKG}/core/checkpoint.py": """
        import os
        from pytorch_distributed_training_example_tpu.utils import resilience

        def write_commit(path, step):
            with open(path, "w") as fh:
                fh.write(str(step))
            os.rename(path, path + ".done")

        def commit(path, step):
            resilience.retriable_io(write_commit, path, step,
                                    _what="ckpt_commit")
    """})
    assert rules(found) == []


def test_gl002_other_paths_not_in_scope(tmp_path):
    found = lint_tree(tmp_path, {f"{PKG}/data/loader.py": """
        def read(path):
            with open(path) as fh:
                return fh.read()
    """})
    assert rules(found) == []


# -- GL003: host-sync in step-scope modules ---------------------------------

def test_gl003_sync_primitives_flagged(tmp_path):
    found = lint_tree(tmp_path, {f"{PKG}/ops/myop.py": """
        import jax

        def bad_metrics(x):
            v = jax.device_get(x)
            w = x.item()
            x.block_until_ready()
            return v, w
    """})
    assert rules(found) == ["GL003", "GL003", "GL003"]
    assert all(f.severity == "error" for f in found)


def test_gl003_float_of_computed_is_info_and_main_exempt(tmp_path):
    found = lint_tree(tmp_path, {f"{PKG}/parallel/mine.py": """
        import jax
        import jax.numpy as jnp

        def log_loss(metrics):
            return float(metrics["loss"])

        def main():
            x = jnp.ones(())
            jax.block_until_ready(x)  # CLI self-test: exempt
    """})
    assert [(f.rule, f.severity) for f in found] == [("GL003", "info")]


# -- GL004: knob-threading consistency --------------------------------------

GL004_CONFIG = f"""
    import dataclasses

    @dataclasses.dataclass(frozen=True)
    class Config:
        lr: float = 0.1
        momentum: float = 0.9
"""


def test_gl004_missing_flag_and_orphan_dest(tmp_path):
    found = lint_tree(tmp_path, {
        f"{PKG}/utils/config.py": GL004_CONFIG,
        "main.py": """
            import argparse

            def build_parser():
                p = argparse.ArgumentParser()
                p.add_argument("--lr", type=float, default=None)
                p.add_argument("--learning-rte", type=float, default=None)
                return p
        """,
    })
    msgs = sorted(f.message for f in found)
    assert len(found) == 2 and all(f.rule == "GL004" for f in found)
    assert "'learning_rte' is not a Config field" in msgs[0]
    assert "'momentum' has no main.py CLI flag" in msgs[1]


def test_gl004_complete_threading_is_clean(tmp_path):
    found = lint_tree(tmp_path, {
        f"{PKG}/utils/config.py": GL004_CONFIG,
        "main.py": """
            import argparse

            def build_parser():
                p = argparse.ArgumentParser()
                p.add_argument("--lr", type=float, default=None)
                p.add_argument("--momentum", type=float, default=None)
                return p
        """,
    })
    assert rules(found) == []


# -- GL005: wall-clock / unseeded randomness --------------------------------

def test_gl005_unseeded_randomness_flagged(tmp_path):
    found = lint_tree(tmp_path, {f"{PKG}/utils/chaos.py": """
        import random
        import time

        import numpy as np

        def jitter():
            return time.time() + random.random() + np.random.uniform()
    """})
    assert rules(found) == ["GL005", "GL005", "GL005"]


def test_gl005_seeded_generators_clean(tmp_path):
    found = lint_tree(tmp_path, {f"{PKG}/data/sampler.py": """
        import time

        import numpy as np

        def order(seed, epoch, n):
            rng = np.random.default_rng((seed, epoch))
            t0 = time.monotonic()  # durations are fine, wall-clock isn't
            return rng.permutation(n), t0
    """})
    assert rules(found) == []


# -- IR rules on tiny planted programs --------------------------------------

@pytest.fixture(scope="module")
def tiny():
    import jax
    import jax.numpy as jnp

    state = {"w": jax.ShapeDtypeStruct((128, 256), jnp.float32),
             "m": jax.ShapeDtypeStruct((128, 256), jnp.float32)}
    batch = jax.ShapeDtypeStruct((8, 128), jnp.bfloat16)
    return jax, jnp, state, batch


@pytest.fixture
def compiled_afresh():
    """The persistent compile cache off for one test: its key leaves
    ``op_name`` metadata out, so an entry that one scope's program wrote
    (any compile over conftest's half second, under load) is read back for
    its siblings that differ in nothing else, and GL105 reads that scope."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", True)
    cc.reset_cache()


def _step_ok(jnp):
    def step(s, b):
        g = (b @ s["w"].astype(jnp.bfloat16)).astype(jnp.float32).sum(0)
        return {"w": s["w"] - 1e-3 * g, "m": s["m"] * 0.9}, jnp.float32(0)
    return step


def test_ir_planted_missing_donation(tiny):
    jax, jnp, state, batch = tiny
    lowered = jax.jit(_step_ok(jnp)).lower(state, batch)  # no donate_argnums
    found = graftlint.lint_lowered("t", lowered, abstract_state=state)
    gl101 = [f for f in found if f.rule == "GL101"]
    assert gl101 and gl101[0].severity == "error"
    assert "not aliased" in gl101[0].message


def test_ir_donated_state_is_clean(tiny):
    jax, jnp, state, batch = tiny
    lowered = jax.jit(_step_ok(jnp), donate_argnums=0).lower(state, batch)
    found = graftlint.lint_lowered("t", lowered, abstract_state=state)
    assert not [f for f in found if f.rule == "GL101" and f.severity == "error"]


def test_ir_planted_fp32_upcast_in_bf16_region(tiny):
    jax, jnp, state, batch = tiny

    def step(s, b):
        with jax.named_scope("moe_router"):
            h = b.astype(jnp.float32) @ s["w"]  # planted forward leak
        return {"w": s["w"] - h.sum(0) * 0, "m": s["m"]}, jnp.float32(0)

    lowered = jax.jit(step, donate_argnums=0).lower(state, batch)
    found = graftlint.lint_lowered("t", lowered, abstract_state=state,
                                   upcast_bytes=1024)
    gl102 = [f for f in found if f.rule == "GL102"]
    assert gl102 and gl102[0].scope == "moe_router"
    assert gl102[0].severity == "error"


def test_ir_accumulating_bf16_dot_not_flagged(tiny):
    """bf16 x bf16 einsum with preferred_element_type=f32 is the
    accumulation contract working — must not be reported as a leak."""
    jax, jnp, state, batch = tiny

    def step(s, b):
        with jax.named_scope("moe_experts"):
            h = jnp.einsum("tb,bf->tf", b, s["w"].astype(jnp.bfloat16),
                           preferred_element_type=jnp.float32)
        return ({"w": s["w"] - h.astype(jnp.float32).sum(0) * 0,
                 "m": s["m"]}, jnp.float32(0))

    lowered = jax.jit(step, donate_argnums=0).lower(state, batch)
    found = graftlint.lint_lowered("t", lowered, abstract_state=state,
                                   upcast_bytes=1024)
    assert not [f for f in found if f.rule == "GL102"]


def test_ir_host_callback_flagged(tiny):
    jax, jnp, state, batch = tiny
    from jax.experimental import io_callback

    def step(s, b):
        io_callback(lambda x: None, None, b.sum())
        return s, jnp.float32(0)

    lowered = jax.jit(step, donate_argnums=0).lower(state, batch)
    found = graftlint.lint_lowered("t", lowered, abstract_state=state)
    gl103 = [f for f in found if f.rule == "GL103"]
    assert gl103 and gl103[0].severity == "error"


def test_ir_sharding_coverage_and_missing(tiny):
    jax, jnp, state, batch = tiny
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(jax.devices(), ("data",))

    def constrained(s, b):
        with jax.named_scope("moe_dispatch"):
            h = jax.lax.with_sharding_constraint(
                b, NamedSharding(mesh, P("data", None)))
        return s, h.astype(jnp.float32).sum()

    lowered = jax.jit(constrained, donate_argnums=0).lower(state, batch)
    found = graftlint.lint_lowered("t", lowered, abstract_state=state,
                                   expect_sharding=True)
    gl104 = [f for f in found if f.rule == "GL104"]
    assert gl104 and gl104[0].severity == "info"
    assert "moe_dispatch=1" in gl104[0].message

    lowered = jax.jit(_step_ok(jnp), donate_argnums=0).lower(state, batch)
    found = graftlint.lint_lowered("t", lowered, abstract_state=state,
                                   expect_sharding=True)
    gl104 = [f for f in found if f.rule == "GL104"]
    assert gl104 and gl104[0].severity == "error"


def _a2a_step(jax, jnp, mesh, scope):
    """Planted 2-device shard_map step whose body issues one all-to-all,
    optionally inside ``scope`` (GL105's sanction vocabulary)."""
    from jax.sharding import PartitionSpec as P

    def body(x):
        import contextlib
        ctx = jax.named_scope(scope) if scope else contextlib.nullcontext()
        with ctx:
            return jax.lax.all_to_all(x, "expert", split_axis=0,
                                      concat_axis=0, tiled=True)

    def step(s, b):
        y = jax.shard_map(body, mesh=mesh, in_specs=P("expert", None),
                          out_specs=P("expert", None), check_vma=False)(b)
        return s, y.astype(jnp.float32).sum()

    return step


def _cperm_step(jax, jnp, mesh, scope):
    """Planted 2-device shard_map step issuing one collective-permute,
    optionally inside ``scope`` (GL105's ring/pp sanction vocabulary)."""
    from jax.sharding import PartitionSpec as P

    def body(x):
        import contextlib
        ctx = jax.named_scope(scope) if scope else contextlib.nullcontext()
        with ctx:
            return jax.lax.ppermute(x, "context", [(0, 1), (1, 0)])

    def step(s, b):
        y = jax.shard_map(body, mesh=mesh, in_specs=P("context", None),
                          out_specs=P("context", None), check_vma=False)(b)
        return s, y.astype(jnp.float32).sum()

    return step


@pytest.mark.parametrize("scope", [None, "attn_ring_ppermute",
                                   "pp_stage_shift"])
def test_ir_cperm_scope_rule(tiny, scope, compiled_afresh):
    """GL105 (r20): an untagged collective-permute is an error; the ring
    K/V rotation and GPipe stage-hop scopes are sanctioned."""
    import numpy as np
    from jax.sharding import Mesh

    jax, jnp, state, batch = tiny
    mesh = Mesh(np.asarray(jax.devices()[:2]), ("context",))
    lowered = jax.jit(_cperm_step(jax, jnp, mesh, scope),
                      donate_argnums=0).lower(state, batch)
    found = graftlint.lint_lowered("t", lowered, abstract_state=state)
    gl105 = [f for f in found if f.rule == "GL105"]
    if scope is None:
        assert gl105 and gl105[0].severity == "error"
        assert "collective-permute outside sanctioned" in gl105[0].message
    else:
        assert gl105 == [], [f.render() for f in gl105]


def test_ir_sharding_seq_census(tiny):
    """GL104 (r20): on a context>1 mesh the coverage census counts
    sequence-dim constraints; zero seq anchors is an error."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    jax, jnp, state, batch = tiny
    mesh = Mesh(np.asarray(jax.devices()[:2]).reshape(1, 2),
                ("data", "context"))
    b3 = jax.ShapeDtypeStruct((4, 64, 32), jnp.bfloat16)

    def seq_anchored(s, b):
        h = jax.lax.with_sharding_constraint(
            b, NamedSharding(mesh, P("data", "context", None)))
        return s, h.astype(jnp.float32).sum()

    lowered = jax.jit(seq_anchored, donate_argnums=0).lower(state, b3)
    found = graftlint.lint_lowered("t", lowered, abstract_state=state,
                                   expect_sharding=True, seq_axis=True)
    gl104 = [f for f in found if f.rule == "GL104"]
    assert gl104 and gl104[0].severity == "info"
    assert "seq-dim=1" in gl104[0].message

    def batch_only(s, b):
        h = jax.lax.with_sharding_constraint(
            b, NamedSharding(mesh, P("data", None, None)))
        return s, h.astype(jnp.float32).sum()

    lowered = jax.jit(batch_only, donate_argnums=0).lower(state, b3)
    found = graftlint.lint_lowered("t", lowered, abstract_state=state,
                                   expect_sharding=True, seq_axis=True)
    errs = [f for f in found if f.rule == "GL104" and f.severity == "error"]
    assert errs and "no sharding constraint splits the sequence dim" in (
        errs[0].message)


@pytest.mark.parametrize("scope", [None, "attn_ulysses_a2a"])
def test_ir_a2a_scope_rule(tiny, scope, compiled_afresh):
    """GL105: an untagged all-to-all is an error; the Ulysses scope is
    sanctioned (its bytes are census-attributable)."""
    import numpy as np
    from jax.sharding import Mesh

    jax, jnp, state, batch = tiny
    mesh = Mesh(np.asarray(jax.devices()[:2]), ("expert",))
    lowered = jax.jit(_a2a_step(jax, jnp, mesh, scope),
                      donate_argnums=0).lower(state, batch)
    found = graftlint.lint_lowered("t", lowered, abstract_state=state)
    gl105 = [f for f in found if f.rule == "GL105"]
    if scope is None:
        assert gl105 and gl105[0].severity == "error"
        assert gl105[0].scope == "a2a-scope"
        assert "all-to-all outside sanctioned" in gl105[0].message
    else:
        assert gl105 == [], [f.render() for f in gl105]


# -- whole-tree gate + baseline workflow ------------------------------------

def test_lint_ir_lowers_the_trainers_program():
    """``run_ir`` lowers what the ``Trainer`` would run for this model (the
    one builder, shapes in the state's place): the state is donated whole,
    nothing crosses to the host, and on 8 devices ``fsdp`` anchors layouts."""
    found = graftlint.run_ir("llama_tiny", seq_len=32)
    assert not [f for f in found if f.severity == graftlint.ERROR], found
    assert [f.rule for f in found if f.scope == "sharding"] == ["GL104"]


def test_whole_tree_zero_unbaselined_errors():
    findings = graftlint.run_ast(REPO)
    baseline = graftlint.load_baseline()
    unbaselined, baselined, stale = graftlint.split_findings(findings,
                                                            baseline)
    errors = [f.render() for f in unbaselined if f.severity == "error"]
    assert errors == [], "unbaselined graftlint errors:\n" + "\n".join(errors)
    assert stale == [], f"stale suppressions (refresh with --record): {stale}"


def test_baseline_has_no_unreviewed_entries():
    baseline = graftlint.load_baseline()
    assert baseline["suppressions"], "expected a non-empty reviewed baseline"
    bad = [s for s in baseline["suppressions"]
           if s.get("justification", "").startswith("UNREVIEWED")
           or not s.get("justification")]
    assert bad == [], bad


def test_record_baseline_preserves_justifications(tmp_path):
    f = graftlint.Finding(rule="GL002", path="x.py", line=3, scope="f",
                          message="m", snippet="open(p)")
    path = str(tmp_path / "b.json")
    graftlint.record_baseline([f], path)
    doc = graftlint.load_baseline(path)
    assert doc["suppressions"][0]["justification"].startswith("UNREVIEWED")
    doc["suppressions"][0]["justification"] = "reviewed: fine"
    json.dump(doc, open(path, "w"))
    graftlint.record_baseline([f], path)
    doc = graftlint.load_baseline(path)
    assert doc["suppressions"][0]["justification"] == "reviewed: fine"
    # findings match the recorded baseline -> gate passes
    unbaselined, _, stale = graftlint.split_findings([f], doc)
    assert unbaselined == [] and stale == []


# -- CLI gates (the tier-1 shell of graftlint.py + check_regression) --------

def test_cli_graftlint_ast_clean_on_head():
    res = subprocess.run(
        [sys.executable, os.path.join("benchmarks", "graftlint.py")],
        cwd=REPO, capture_output=True, text=True, timeout=120)
    assert res.returncode == 0, res.stdout + res.stderr
    assert "0 unbaselined error(s)" in res.stdout


def test_cli_check_regression_lint_pass_and_fail(tmp_path):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    res = subprocess.run(
        [sys.executable, os.path.join("benchmarks", "check_regression.py"),
         "--lint"],
        cwd=REPO, capture_output=True, text=True, timeout=120, env=env)
    assert res.returncode == 0, res.stdout + res.stderr
    assert "LINT" in res.stdout

    bad_root = tmp_path / "tree"
    bad = bad_root / PKG / "core" / "checkpoint.py"
    bad.parent.mkdir(parents=True)
    bad.write_text("def f(p):\n    return open(p).read()\n")
    empty = tmp_path / "baseline.json"
    empty.write_text('{"suppressions": []}\n')
    res = subprocess.run(
        [sys.executable, os.path.join("benchmarks", "check_regression.py"),
         "--lint", "--lint-root", str(bad_root),
         "--lint-baseline", str(empty)],
        cwd=REPO, capture_output=True, text=True, timeout=120, env=env)
    assert res.returncode == 1, res.stdout + res.stderr
    assert "LINT-FAIL" in res.stdout and "GL002" in res.stdout

    # --record refreshes the baseline; the same tree then gates clean.
    res = subprocess.run(
        [sys.executable, os.path.join("benchmarks", "check_regression.py"),
         "--lint", "--lint-root", str(bad_root),
         "--lint-baseline", str(empty), "--record"],
        cwd=REPO, capture_output=True, text=True, timeout=120, env=env)
    assert res.returncode == 0, res.stdout + res.stderr
    assert "RECORDED" in res.stdout
    res = subprocess.run(
        [sys.executable, os.path.join("benchmarks", "check_regression.py"),
         "--lint", "--lint-root", str(bad_root),
         "--lint-baseline", str(empty)],
        cwd=REPO, capture_output=True, text=True, timeout=120, env=env)
    assert res.returncode == 0, res.stdout + res.stderr
