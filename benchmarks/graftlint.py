#!/usr/bin/env python
"""graftlint: repo-specific two-layer static analysis.

Layer 1 (AST, stdlib-only, jax-free) walks the Python tree and enforces
hazard rules distilled from this repo's postmortems:

  GL001  zero-copy ``np.asarray``/``memoryview`` snapshots escaping into a
         background thread / async writer (the r11 checkpoint-corruption
         class; snapshots of donated device buffers must be ``np.array``
         copies).
  GL002  filesystem ops in checkpoint/resilience paths that bypass
         ``retriable_io`` (transient NFS/GCS-fuse errors must be retried
         or explicitly baselined).
  GL003  host-sync primitives (``jax.device_get``, ``.item()``,
         ``block_until_ready``, ``float()``/``int()`` of traced values)
         inside step-scope modules (train_loop / parallel / ops).
  GL004  knob-threading consistency: every ``utils/config.py`` field must
         be reachable from the ``main.py`` CLI, and every CLI dest must map
         to a real Config field (``config_from_args`` silently drops
         strangers).
  GL005  wall-clock / unseeded randomness in seeded chaos & sampler paths
         (breaks same-seed ``chaos.jsonl`` diffing).

Layer 2 (IR) lowers the ``Trainer``'s own step program on shapes, with no
chip and no parameter in memory (``core/trainer.build_step_program``), and
inspects its optimized HLO / StableHLO:

  GL101  donation coverage: state inputs not aliased to outputs
         (double-HBM residency).
  GL102  large fp32 ``convert`` results inside bf16-configured MoE regions
         (the r10 router-leak class, keyed on ``jax.named_scope`` tags).
  GL103  device-to-host transfers (host callbacks / outfeed) baked into
         the compiled step.
  GL104  sharding-constraint coverage per named-scope region; on a
         context>1 mesh the census also counts sequence-dim constraints
         (zero seq anchors at such a mesh is an error).
  GL105  unattributable point-to-point collectives: every ``all-to-all``
         (sanctioned scope: ``attn_ulysses_a2a`` for Ulysses) and every
         ``collective-permute`` (``attn_ring_ppermute`` for the ring
         K/V rotation, ``pp_stage_shift`` for the GPipe hop) in the
         compiled step must carry
         a sanctioned named-scope tag in its op_name metadata — an
         untagged collective evades the comms census (``--aot-bytes``)
         and the per-region profile rollups.

Findings are machine-readable (``--json``) and gated against a reviewed
suppression baseline (``benchmarks/lint_baseline.json``); each suppression
carries a one-line justification.  ``check_regression.py --lint`` wraps
this module for CI.

Usage:
  python benchmarks/graftlint.py                 # AST layer, gate vs baseline
  python benchmarks/graftlint.py --ir afmoe_tiny
  python benchmarks/graftlint.py --all           # AST + IR (afmoe_tiny)
  python benchmarks/graftlint.py --json          # machine-readable findings
"""
from __future__ import annotations

import argparse
import ast
import dataclasses
import json
import os
import re
import sys

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = "pytorch_distributed_training_example_tpu"
DEFAULT_BASELINE = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "lint_baseline.json"
)

ERROR = "error"
INFO = "info"

# Region tags used by the AOT byte gate; the IR layer keys GL102/GL104 on
# the same vocabulary so findings line up with check_regression --aot-bytes:
# the expert layer's scopes (parallel/moe.py).
MOE_TAG_RE = re.compile(r"\bmoe_(router|dispatch|experts|combine|shared)\b")

# Scope sanctioned to issue all-to-all (GL105): the Ulysses head<->sequence
# reshard (ops/attention.py). Cotangent a2as keep the forward scope path
# inside transpose(...), so backward ops match too. The expert layer has no
# exchange across chips (ROADMAP B4 (1)): one written under a ``moe_*``
# scope is a finding until it is sanctioned here.
A2A_SCOPE_RE = re.compile(r"\battn_ulysses_a2a\b")

# Scopes sanctioned to issue collective-permute (GL105, r22): the ring /
# zigzag K-V rotation and output un-permute (``attn_ring_ppermute``,
# ops/attention.py) and the GPipe stage hop (``pp_stage_shift``,
# parallel/pipeline.py). ``attn_ring_allgather`` (the ring's dense
# fallback) rides along so an attention-site gather stays
# census-attributable too.
CPERM_SCOPE_RE = re.compile(
    r"\b(?:attn_ring_ppermute|attn_ring_allgather|pp_stage_shift)\b")


def _norm(s: str) -> str:
    return re.sub(r"\s+", " ", s.strip())


@dataclasses.dataclass
class Finding:
    rule: str
    path: str  # repo-relative posix path, or "<ir:label>" for IR findings
    line: int
    scope: str
    message: str
    severity: str = ERROR
    snippet: str = ""

    @property
    def fingerprint(self) -> str:
        return "|".join((self.rule, self.path, self.scope, _norm(self.snippet)))

    def to_dict(self) -> dict:
        d = dataclasses.asdict(self)
        d["fingerprint"] = self.fingerprint
        return d

    def render(self) -> str:
        sev = "" if self.severity == ERROR else f" [{self.severity}]"
        return f"{self.path}:{self.line}: {self.rule}{sev} {self.message} (in {self.scope})"


# ---------------------------------------------------------------------------
# AST plumbing
# ---------------------------------------------------------------------------

_FUNC_NODES = (ast.FunctionDef, ast.AsyncFunctionDef)
_SCOPE_NODES = _FUNC_NODES + (ast.ClassDef,)


def _dotted(node) -> str | None:
    """'np.asarray' for Attribute/Name chains, else None."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


class Module:
    """Parsed module with parent links and dotted scope names."""

    def __init__(self, root: str, relpath: str):
        self.relpath = relpath
        with open(os.path.join(root, relpath), "r", encoding="utf-8") as f:
            self.source = f.read()
        self.lines = self.source.splitlines()
        self.tree = ast.parse(self.source, filename=relpath)
        self.parent: dict[ast.AST, ast.AST] = {}
        self.scope_name: dict[ast.AST, str] = {self.tree: "<module>"}
        self._annotate(self.tree, "<module>")

    def _annotate(self, node: ast.AST, scope: str) -> None:
        for child in ast.iter_child_nodes(node):
            self.parent[child] = node
            child_scope = scope
            if isinstance(child, _SCOPE_NODES):
                child_scope = (
                    child.name if scope == "<module>" else f"{scope}.{child.name}"
                )
            self.scope_name[child] = child_scope
            self._annotate(child, child_scope)

    def scope_of(self, node: ast.AST) -> str:
        # The scope a node *belongs to* is the name of its innermost
        # enclosing def/class (scope_name stores the scope the node opens,
        # for defs themselves, which is what we want for findings anyway).
        return self.scope_name.get(node, "<module>")

    def line_text(self, lineno: int) -> str:
        if 1 <= lineno <= len(self.lines):
            return self.lines[lineno - 1].strip()
        return ""

    def finding(self, rule, node, message, severity=ERROR) -> Finding:
        return Finding(
            rule=rule,
            path=self.relpath,
            line=getattr(node, "lineno", 0),
            scope=self.scope_of(node),
            message=message,
            severity=severity,
            snippet=self.line_text(getattr(node, "lineno", 0)),
        )

    def statement_of(self, node: ast.AST) -> ast.stmt | None:
        cur = node
        while cur is not None and not isinstance(cur, ast.stmt):
            cur = self.parent.get(cur)
        return cur

    def enclosing_defs(self, node: ast.AST):
        cur = self.parent.get(node)
        while cur is not None:
            if isinstance(cur, _FUNC_NODES):
                yield cur
            cur = self.parent.get(cur)


def _iter_own_nodes(unit: ast.AST):
    """All descendant nodes of `unit` that are not inside a nested def."""
    stack = list(ast.iter_child_nodes(unit))
    while stack:
        node = stack.pop()
        yield node
        if not isinstance(node, _FUNC_NODES):
            stack.extend(ast.iter_child_nodes(node))


def _scope_units(tree: ast.Module):
    """Yield (node,) for the module and every function at any depth."""
    yield tree
    for node in ast.walk(tree):
        if isinstance(node, _FUNC_NODES):
            yield node


def _bound_names(func: ast.AST) -> set[str]:
    bound: set[str] = set()
    if isinstance(func, _FUNC_NODES):
        a = func.args
        for arg in (
            list(a.posonlyargs) + list(a.args) + list(a.kwonlyargs)
        ):
            bound.add(arg.arg)
        if a.vararg:
            bound.add(a.vararg.arg)
        if a.kwarg:
            bound.add(a.kwarg.arg)
    for node in _iter_own_nodes(func):
        if isinstance(node, ast.Name) and isinstance(node.ctx, (ast.Store, ast.Del)):
            bound.add(node.id)
        elif isinstance(node, _FUNC_NODES + (ast.ClassDef,)):
            bound.add(node.name)
        elif isinstance(node, (ast.Import, ast.ImportFrom)):
            for alias in node.names:
                bound.add((alias.asname or alias.name).split(".")[0])
    return bound


def _free_reads(func: ast.AST) -> set[str]:
    bound = _bound_names(func)
    free: set[str] = set()
    for node in _iter_own_nodes(func):
        if isinstance(node, ast.Name) and isinstance(node.ctx, ast.Load):
            if node.id not in bound:
                free.add(node.id)
        elif isinstance(node, _FUNC_NODES):
            # Nested defs inherit the closure: their free reads are ours
            # too unless bound here.
            free |= {n for n in _free_reads(node) if n not in bound}
    return free


# ---------------------------------------------------------------------------
# GL001: zero-copy snapshots escaping to background threads
# ---------------------------------------------------------------------------

_ZERO_COPY = {"np.asarray", "numpy.asarray", "jnp.asarray", "memoryview"}
_MUTATORS = {"append", "extend", "add", "update", "setdefault", "insert", "put"}


def _gl001(mod: Module) -> list[Finding]:
    out: list[Finding] = []
    for unit in _scope_units(mod.tree):
        local_defs: dict[str, ast.AST] = {
            n.name: n for n in _iter_own_nodes(unit) if isinstance(n, _FUNC_NODES)
        }
        if not local_defs:
            continue
        # Thread / executor targets launched from this scope.
        target_names: set[str] = set()
        launch_calls: list[ast.Call] = []
        for node in _iter_own_nodes(unit):
            if not isinstance(node, ast.Call):
                continue
            callee = _dotted(node.func) or ""
            is_thread = callee.endswith("Thread") or callee.endswith("Process")
            is_submit = isinstance(node.func, ast.Attribute) and node.func.attr in (
                "submit",
                "apply_async",
            )
            if not (is_thread or is_submit):
                continue
            launch_calls.append(node)
            for kw in node.keywords:
                if kw.arg == "target" and isinstance(kw.value, ast.Name):
                    target_names.add(kw.value.id)
            if is_submit and node.args and isinstance(node.args[0], ast.Name):
                target_names.add(node.args[0].id)
        async_defs = []
        seen: set[str] = set()
        frontier = [n for n in target_names if n in local_defs]
        while frontier:
            name = frontier.pop()
            if name in seen:
                continue
            seen.add(name)
            fn = local_defs[name]
            async_defs.append(fn)
            for node in ast.walk(fn):
                if isinstance(node, ast.Call) and isinstance(node.func, ast.Name):
                    if node.func.id in local_defs and node.func.id not in seen:
                        frontier.append(node.func.id)
        if not async_defs:
            continue
        free: set[str] = set()
        for fn in async_defs:
            free |= _free_reads(fn)
        async_nodes = set()
        for fn in async_defs:
            async_nodes.update(ast.walk(fn))
        # Pass 1: zero-copy calls whose results land directly in a name the
        # async defs read; also taint locals that hold the result (the real
        # r11 shape flowed through one: regions.append((idx, np.asarray(
        # sh.data))); ...; shards[path] = regions).
        flagged: set[ast.Call] = set()
        tainted: dict[str, ast.Call] = {}
        for node in _iter_own_nodes(unit):
            if node in async_nodes or not isinstance(node, ast.Call):
                continue
            callee = _dotted(node.func)
            if callee not in _ZERO_COPY:
                continue
            sink = _escape_sink(mod, node, free, launch_calls)
            if sink is not None:
                flagged.add(node)
                out.append(_gl001_finding(mod, node, callee, sink))
                continue
            stmt = mod.statement_of(node)
            if isinstance(stmt, ast.Assign):
                for t in stmt.targets:
                    if isinstance(t, ast.Name):
                        tainted[t.id] = node
            elif (
                isinstance(stmt, ast.Expr)
                and isinstance(stmt.value, ast.Call)
                and isinstance(stmt.value.func, ast.Attribute)
                and stmt.value.func.attr in _MUTATORS
                and isinstance(stmt.value.func.value, ast.Name)
            ):
                tainted[stmt.value.func.value.id] = node
        # Pass 2 (one hop): a tainted local flowing into a free name.
        for node in _iter_own_nodes(unit):
            if not tainted or node in async_nodes:
                continue
            sink, value = None, None
            if isinstance(node, ast.Assign):
                for t in node.targets:
                    base = t
                    while isinstance(base, (ast.Subscript, ast.Attribute,
                                            ast.Starred)):
                        base = base.value
                    if isinstance(base, ast.Name) and base.id in free:
                        sink = base.id
                value = node.value
            elif (
                isinstance(node, ast.Expr)
                and isinstance(node.value, ast.Call)
                and isinstance(node.value.func, ast.Attribute)
                and node.value.func.attr in _MUTATORS
                and isinstance(node.value.func.value, ast.Name)
                and node.value.func.value.id in free
            ):
                sink = node.value.func.value.id
                value = node.value
            if sink is None or value is None:
                continue
            for name_node in ast.walk(value):
                if (
                    isinstance(name_node, ast.Name)
                    and name_node.id in tainted
                    and tainted[name_node.id] not in flagged
                    and not _consumed_between(mod, name_node, node)
                ):
                    call = tainted[name_node.id]
                    flagged.add(call)
                    out.append(
                        _gl001_finding(
                            mod, call, _dotted(call.func),
                            f"{name_node.id} -> {sink}"))
    return out


def _consumed_between(mod, node, stmt) -> bool:
    """True if a Call swallows `node`'s value between it and `stmt`'s
    assignment value / mutator args (copies like np.array(x) de-taint)."""
    anc = mod.parent.get(node)
    while anc is not None and anc is not stmt:
        if isinstance(anc, ast.Call):
            # The mutator call itself (free.append(tainted)) doesn't consume.
            parent = mod.parent.get(anc)
            is_stmt_call = (
                isinstance(stmt, ast.Expr) and anc is stmt.value
            )
            del parent
            return not is_stmt_call
        anc = mod.parent.get(anc)
    return False


def _gl001_finding(mod, node, callee, sink) -> Finding:
    return mod.finding(
        "GL001",
        node,
        f"zero-copy {callee}(...) escapes to a background thread via "
        f"'{sink}'; a donated/updated device buffer behind it can be "
        "overwritten mid-write — snapshot with np.array(...) instead "
        "(r11 corruption class)",
    )


def _escape_sink(mod, call, free, launch_calls):
    """Name through which `call`'s result reaches the async scope, or None."""
    # Direct argument of the Thread(...)/submit(...) launch itself.
    for lc in launch_calls:
        if any(call in ast.walk(a) for a in list(lc.args) + [k.value for k in lc.keywords]):
            return _dotted(lc.func) or "<launch>"
    stmt = mod.statement_of(call)
    if stmt is None:
        return None

    def consumed_before(outer) -> bool:
        # True if another call swallows the result between `call` and
        # `outer` (e.g. str(np.asarray(x).dtype)): no raw buffer escapes.
        anc = mod.parent.get(call)
        while anc is not None and anc is not outer:
            if isinstance(anc, ast.Call):
                return True
            anc = mod.parent.get(anc)
        return False

    if isinstance(stmt, (ast.Assign, ast.AnnAssign, ast.AugAssign)):
        if consumed_before(stmt):
            return None
        targets = stmt.targets if isinstance(stmt, ast.Assign) else [stmt.target]
        for t in targets:
            base = t
            while isinstance(base, (ast.Subscript, ast.Attribute, ast.Starred)):
                base = base.value
            if isinstance(base, ast.Name) and base.id in free:
                return base.id
    if isinstance(stmt, ast.Expr) and isinstance(stmt.value, ast.Call):
        f = stmt.value.func
        if (
            isinstance(f, ast.Attribute)
            and f.attr in _MUTATORS
            and isinstance(f.value, ast.Name)
            and f.value.id in free
            and any(call in ast.walk(a) for a in stmt.value.args)
            and not consumed_before(stmt.value)
        ):
            return f.value.id
    return None


# ---------------------------------------------------------------------------
# GL002: fs ops bypassing retriable_io in checkpoint/resilience paths
# ---------------------------------------------------------------------------

GL002_PATHS = (f"{PKG}/core/checkpoint.py", f"{PKG}/utils/resilience.py",
               f"{PKG}/utils/scheduler.py", f"{PKG}/core/xcache.py",
               f"{PKG}/core/reshard.py", "launch.py")
_FS_OPS = {
    "open",
    "os.replace",
    "os.rename",
    "os.makedirs",
    "os.remove",
    "os.unlink",
    "os.rmdir",
    "os.listdir",
    "shutil.rmtree",
    "shutil.copy",
    "shutil.copy2",
    "shutil.copyfile",
    "shutil.move",
    "np.save",
    "np.load",
    "numpy.save",
    "numpy.load",
}


def _gl002(mod: Module) -> list[Finding]:
    if mod.relpath not in GL002_PATHS:
        return []
    # Functions whose *name* is handed to retriable_io anywhere in the
    # module are retry-wrapped at their call sites; their bodies are exempt.
    wrapped: set[str] = set()
    for node in ast.walk(mod.tree):
        if isinstance(node, ast.Call):
            callee = _dotted(node.func) or ""
            if callee.split(".")[-1] == "retriable_io" and node.args:
                first = _dotted(node.args[0])
                if first and "." not in first:
                    wrapped.add(first)
    out: list[Finding] = []
    for node in ast.walk(mod.tree):
        if not isinstance(node, ast.Call):
            continue
        callee = _dotted(node.func)
        if callee not in _FS_OPS:
            continue
        if callee == "shutil.rmtree" and any(
            kw.arg == "ignore_errors"
            and isinstance(kw.value, ast.Constant)
            and kw.value.value is True
            for kw in node.keywords
        ):
            continue  # explicitly best-effort
        if any(d.name in wrapped or d.name == "retriable_io"
               for d in mod.enclosing_defs(node)):
            continue
        out.append(
            mod.finding(
                "GL002",
                node,
                f"filesystem op {callee}(...) in a checkpoint/resilience "
                "path bypasses retriable_io; transient NFS/object-store "
                "errors will abort the job instead of retrying",
            )
        )
    return out


# ---------------------------------------------------------------------------
# GL003: host-sync primitives in step-scope modules
# ---------------------------------------------------------------------------

GL003_PREFIXES = (f"{PKG}/core/train_loop.py", f"{PKG}/parallel/", f"{PKG}/ops/",
                  f"{PKG}/serve/")
_SYNC_CALLS = {"jax.device_get", "jax.block_until_ready"}
_SYNC_METHODS = {"item", "block_until_ready"}


def _gl003(mod: Module) -> list[Finding]:
    if not mod.relpath.startswith(GL003_PREFIXES):
        return []
    out: list[Finding] = []
    for node in ast.walk(mod.tree):
        if not isinstance(node, ast.Call):
            continue
        if any(d.name in ("main", "_selftest") for d in mod.enclosing_defs(node)):
            continue
        callee = _dotted(node.func)
        if callee in _SYNC_CALLS:
            out.append(
                mod.finding(
                    "GL003",
                    node,
                    f"host-sync {callee}(...) in a step-scope module blocks "
                    "the dispatch pipeline (device->host round trip inside "
                    "or around the jitted step)",
                )
            )
        elif (
            isinstance(node.func, ast.Attribute)
            and node.func.attr in _SYNC_METHODS
            and not node.args
        ):
            out.append(
                mod.finding(
                    "GL003",
                    node,
                    f".{node.func.attr}() in a step-scope module forces a "
                    "host sync; keep metrics on-device and sync once per "
                    "logging interval",
                )
            )
        elif (
            isinstance(node.func, ast.Name)
            and node.func.id in ("float", "int")
            and len(node.args) == 1
            and isinstance(node.args[0], (ast.Call, ast.Subscript))
        ):
            out.append(
                mod.finding(
                    "GL003",
                    node,
                    f"{node.func.id}(...) of a computed value in a "
                    "step-scope module is a host sync if the operand is a "
                    "tracer/device array",
                    severity=INFO,
                )
            )
    return out


# ---------------------------------------------------------------------------
# GL004: knob-threading consistency between utils/config.py and main.py
# ---------------------------------------------------------------------------

# CLI dests in main.py that intentionally do not map to Config fields
# (process bootstrap / composite parses).
GL004_INFRA_DESTS = {
    "distributed",
    "config",
    "mesh",
    "coordinator",
    "num_processes",
    "process_id",
    "platform",
    "fake_devices",
}


def _parser_dests(tree: ast.Module) -> dict[str, int]:
    dests: dict[str, int] = {}
    for node in ast.walk(tree):
        if not (
            isinstance(node, ast.Call)
            and isinstance(node.func, ast.Attribute)
            and node.func.attr == "add_argument"
        ):
            continue
        dest = None
        for kw in node.keywords:
            if kw.arg == "dest" and isinstance(kw.value, ast.Constant):
                dest = kw.value.value
        if dest is None:
            for a in node.args:
                if (
                    isinstance(a, ast.Constant)
                    and isinstance(a.value, str)
                    and a.value.startswith("--")
                ):
                    dest = a.value.lstrip("-").replace("-", "_")
                    break
        if dest:
            dests.setdefault(dest, node.lineno)
    return dests


def _config_fields(tree: ast.Module) -> list[str]:
    for node in ast.walk(tree):
        if isinstance(node, ast.ClassDef) and node.name == "Config":
            return [
                s.target.id
                for s in node.body
                if isinstance(s, ast.AnnAssign)
                and isinstance(s.target, ast.Name)
                and not s.target.id.startswith("_")
            ]
    return []


def _gl004(root: str) -> list[Finding]:
    paths = {"config": f"{PKG}/utils/config.py", "main": "main.py"}
    mods: dict[str, Module] = {}
    for key, rel in paths.items():
        full = os.path.join(root, rel)
        if os.path.exists(full):
            mods[key] = Module(root, rel)
    if "config" not in mods or "main" not in mods:
        return []
    fields = _config_fields(mods["config"].tree)
    if not fields:
        return []
    out: list[Finding] = []
    cfg = mods["config"]
    main = mods["main"]
    main_dests = _parser_dests(main.tree)

    # Direction 1: every main.py CLI dest must be a Config field (or
    # declared infra), else config_from_args silently drops the override.
    for dest, lineno in sorted(main_dests.items()):
        if dest in fields or dest in GL004_INFRA_DESTS:
            continue
        out.append(
            Finding(
                rule="GL004",
                path=main.relpath,
                line=lineno,
                scope="build_parser",
                message=(
                    f"CLI dest '{dest}' is not a Config field; "
                    "config_from_args silently discards it (typo or "
                    "missing field)"
                ),
                snippet=main.line_text(lineno),
            )
        )

    # Direction 2: every Config field must be reachable from main.py.
    mesh_covered = "mesh" in main_dests
    for field in fields:
        if field.startswith("mesh_") and mesh_covered:
            continue  # composite --mesh AXIS=N parse covers mesh_* fields
        if field not in main_dests:
            out.append(
                Finding(
                    rule="GL004",
                    path=cfg.relpath,
                    line=1,
                    scope="Config",
                    message=(
                        f"Config field '{field}' has no main.py CLI flag; "
                        "it cannot be overridden without editing presets"
                    ),
                    snippet=field,
                )
            )
    return out


# ---------------------------------------------------------------------------
# GL005: wall-clock / unseeded randomness in seeded chaos & sampler paths
# ---------------------------------------------------------------------------

GL005_PATHS = (f"{PKG}/utils/chaos.py", f"{PKG}/data/sampler.py",
               f"{PKG}/serve/engine.py", f"{PKG}/serve/loadgen.py",
               f"{PKG}/serve/prefix_cache.py", f"{PKG}/serve/router.py",
               f"{PKG}/serve/slo.py", f"{PKG}/serve/spec_decode.py",
               f"{PKG}/utils/scheduler.py", f"{PKG}/core/reshard.py",
               f"{PKG}/core/xcache.py", "launch.py")
_NP_UNSEEDED = {
    "rand",
    "randn",
    "randint",
    "random",
    "random_sample",
    "choice",
    "shuffle",
    "permutation",
    "uniform",
    "normal",
    "seed",
}


def _gl005(mod: Module) -> list[Finding]:
    if mod.relpath not in GL005_PATHS:
        return []
    out: list[Finding] = []
    for node in ast.walk(mod.tree):
        if not isinstance(node, ast.Call):
            continue
        callee = _dotted(node.func) or ""
        bad = None
        if callee in ("time.time", "time.time_ns", "datetime.now", "datetime.datetime.now"):
            bad = f"wall-clock {callee}() in a seeded path makes same-seed runs diverge"
        elif callee.startswith("random."):
            bad = f"unseeded stdlib {callee}(...) breaks same-seed chaos.jsonl diffing"
        elif (
            callee.startswith(("np.random.", "numpy.random."))
            and callee.split(".")[-1] in _NP_UNSEEDED
        ):
            bad = (
                f"global-state {callee}(...) is unseeded; use a "
                "np.random.default_rng/RandomState seeded from cfg"
            )
        if bad:
            out.append(mod.finding("GL005", node, bad))
    return out


# ---------------------------------------------------------------------------
# AST driver
# ---------------------------------------------------------------------------

EXCLUDE_DIRS = {"__pycache__", "tests", "native", ".git", ".venv", "fixtures"}


def collect_py_files(root: str) -> list[str]:
    out = []
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames[:] = sorted(
            d for d in dirnames if d not in EXCLUDE_DIRS and not d.startswith(".")
        )
        for fn in sorted(filenames):
            if fn.endswith(".py"):
                rel = os.path.relpath(os.path.join(dirpath, fn), root)
                out.append(rel.replace(os.sep, "/"))
    return out


def run_ast(root: str = REPO_ROOT, files: list[str] | None = None) -> list[Finding]:
    findings: list[Finding] = []
    for rel in files if files is not None else collect_py_files(root):
        try:
            mod = Module(root, rel)
        except (SyntaxError, UnicodeDecodeError) as e:
            findings.append(
                Finding(
                    rule="GL000",
                    path=rel,
                    line=getattr(e, "lineno", 0) or 0,
                    scope="<module>",
                    message=f"unparseable: {e}",
                    snippet="",
                )
            )
            continue
        findings += _gl001(mod)
        findings += _gl002(mod)
        findings += _gl003(mod)
        findings += _gl005(mod)
    findings += _gl004(root)
    findings.sort(key=lambda f: (f.path, f.line, f.rule))
    return findings


# ---------------------------------------------------------------------------
# IR layer (lazy jax import; lowers the Trainer's step program on shapes)
# ---------------------------------------------------------------------------

def _entry_block(hlo: str) -> str:
    m = re.search(r"^ENTRY\b.*$", hlo, re.M)
    if not m:
        return hlo
    rest = hlo[m.start():]
    end = re.search(r"^\}", rest, re.M)
    return rest[: end.end()] if end else rest


def _aliased_params(hlo: str) -> set[int]:
    m = re.search(r"input_output_alias=\{", hlo)
    if not m:
        return set()
    depth, i = 1, m.end()
    while i < len(hlo) and depth:
        if hlo[i] == "{":
            depth += 1
        elif hlo[i] == "}":
            depth -= 1
        i += 1
    body = hlo[m.end(): i - 1]
    return {int(p) for p in re.findall(r"\((\d+),", body)}


def _leaf_bytes(leaf) -> int:
    import numpy as _np

    try:
        return int(_np.dtype(leaf.dtype).itemsize * _np.prod(leaf.shape, dtype=_np.int64))
    except Exception:
        return 0


def _ir_donation(hlo, label, abstract_state, slack) -> list[Finding]:
    import jax

    leaves_with_paths = jax.tree_util.tree_flatten_with_path(abstract_state)[0]
    n_state = len(leaves_with_paths)
    aliased = _aliased_params(hlo)
    entry = _entry_block(hlo)
    n_params = len(set(re.findall(r"parameter\((\d+)\)", entry)))
    out: list[Finding] = []
    if n_params < n_state:
        out.append(
            Finding(
                rule="GL101",
                path=f"<ir:{label}>",
                line=0,
                scope="donation",
                message=(
                    f"entry param count {n_params} < state leaf count "
                    f"{n_state}; param mapping uncertain, donation coverage "
                    "checked by count only"
                ),
                severity=INFO,
                snippet="param-mapping",
            )
        )
    missing = [
        (jax.tree_util.keystr(path), _leaf_bytes(leaf))
        for i, (path, leaf) in enumerate(leaves_with_paths)
        if i not in aliased
    ]
    total = sum(_leaf_bytes(leaf) for _, leaf in leaves_with_paths) or 1
    missing_bytes = sum(b for _, b in missing)
    if missing and missing_bytes > slack * total:
        worst = sorted(missing, key=lambda kv: -kv[1])[:5]
        detail = ", ".join(f"{k} ({b/1e6:.2f} MB)" for k, b in worst)
        out.append(
            Finding(
                rule="GL101",
                path=f"<ir:{label}>",
                line=0,
                scope="donation",
                message=(
                    f"{len(missing)}/{n_state} state inputs "
                    f"({missing_bytes/1e6:.2f} of {total/1e6:.2f} MB) are "
                    f"not aliased to outputs — donation gap doubles HBM "
                    f"residency for: {detail}"
                ),
                snippet=f"non-donated={len(missing)}",
            )
        )
    elif missing:
        out.append(
            Finding(
                rule="GL101",
                path=f"<ir:{label}>",
                line=0,
                scope="donation",
                message=(
                    f"{len(missing)}/{n_state} state inputs not aliased "
                    f"({missing_bytes} B, under {slack:.0%} slack): "
                    + ", ".join(k for k, _ in missing[:5])
                ),
                severity=INFO,
                snippet=f"non-donated-small={len(missing)}",
            )
        )
    return out


_CONVERT_RE = re.compile(
    r"= f32\[([\d,]*)\](?:\{[^}]*\})? convert\(.*?op_name=\"([^\"]+)\"", re.S
)


def _ir_upcast(hlo, label, upcast_bytes) -> list[Finding]:
    out: list[Finding] = []
    seen: set[tuple] = set()
    for line in hlo.splitlines():
        m = _CONVERT_RE.search(line)
        if not m:
            continue
        shape_s, op_name = m.groups()
        tag_m = MOE_TAG_RE.search(op_name)
        if not tag_m:
            continue
        # Backward-pass converts (transpose(jvp(...)) scopes) are the
        # mixed-precision grad->fp32-optimizer upcasts, one per param leaf
        # by design; the r10 leak class is *forward* ops computing wide.
        if "transpose(" in op_name:
            continue
        # Only source-level casts/promotions (jaxpr convert_element_type)
        # count: XLA materializes operand upcasts for f32-ACCUMULATING bf16
        # dots (preferred_element_type) and attributes them to the dot op —
        # that is the accumulation contract working, not a leak.
        if not op_name.endswith("convert_element_type"):
            continue
        dims = [int(d) for d in shape_s.split(",") if d] or [1]
        nbytes = 4
        for d in dims:
            nbytes *= d
        if nbytes < upcast_bytes:
            continue
        region = tag_m.group(0)
        key = (region, shape_s)
        if key in seen:
            continue
        seen.add(key)
        out.append(
            Finding(
                rule="GL102",
                path=f"<ir:{label}>",
                line=0,
                scope=region,
                message=(
                    f"fp32 convert to f32[{shape_s}] ({nbytes/1e6:.2f} MB) "
                    f"inside bf16 region '{region}' (op {op_name}) — the "
                    "r10 router-leak class; keep wide math scoped to the "
                    "router softmax or raise the region's declared dtype"
                ),
                snippet=f"convert f32[{shape_s}] {region}",
            )
        )
    return out


def _ir_host_transfer(hlo, label) -> list[Finding]:
    out: list[Finding] = []
    for line in hlo.splitlines():
        hit = None
        m = re.search(r'custom_call_target="([^"]+)"', line)
        if m and ("callback" in m.group(1) or "host" in m.group(1).lower()):
            hit = f"host callback custom-call '{m.group(1)}'"
        elif re.search(r"= \S+ (outfeed|infeed)\(", line):
            hit = "outfeed/infeed"
        if hit is None:
            continue
        op = re.search(r'op_name="([^"]+)"', line)
        out.append(
            Finding(
                rule="GL103",
                path=f"<ir:{label}>",
                line=0,
                scope="host-transfer",
                message=(
                    f"{hit} inside the compiled step"
                    + (f" (op {op.group(1)})" if op else "")
                    + " — device->host transfer serializes every step"
                ),
                snippet=_norm(hit),
            )
        )
    return out


def _ir_sharding(asm, label, expect_sharding, seq_axis=False) -> list[Finding]:
    locs: dict[str, str] = {}
    for m in re.finditer(r"#loc(\d+) = loc\(\"([^\"]+)\"", asm):
        locs[m.group(1)] = m.group(2)
    # Aliased locs: #loc12 = loc(#loc7)
    for m in re.finditer(r"#loc(\d+) = loc\(#loc(\d+)\)", asm):
        if m.group(2) in locs:
            locs.setdefault(m.group(1), locs[m.group(2)])
    counts: dict[str, int] = {}
    total = 0
    seq_total = 0
    # Shardy form: sdy.sharding_constraint %x <@mesh, [{"data"}, {}]> : tensor<..> loc(#locN)
    for m in re.finditer(
        r"sdy\.sharding_constraint[^\n]*?loc\(#loc(\d+)\)", asm
    ):
        total += 1
        scope_s = locs.get(m.group(1), "")
        tag = MOE_TAG_RE.search(scope_s)
        region = tag.group(0) if tag else "untagged"
        counts[region] = counts.get(region, 0) + 1
        # Sequence-axis census (r22): a constraint splitting dim 1 of a
        # rank>=3 operand is anchoring the [B, S, ...] sequence dim — on a
        # context>1 mesh that's the seq/context axis (plus "model" when the
        # Megatron-SP fold is on). The [{..}, {..}, ...] list names the mesh
        # axes per dim in dim order, so dim 1 is anchored when its entry
        # names an axis.
        dims = re.search(r"<@\w+, \[(.*?)\]>", m.group(0))
        rank = re.search(r"tensor<(?:\d+x){3,}", m.group(0))
        if dims and rank:
            per_dim = re.findall(r"\{([^}]*)\}", dims.group(1))
            if len(per_dim) > 1 and '"' in per_dim[1]:
                seq_total += 1
    out: list[Finding] = []
    if seq_axis and total and seq_total == 0:
        out.append(
            Finding(
                rule="GL104",
                path=f"<ir:{label}>",
                line=0,
                scope="sharding",
                message=(
                    "mesh has a context axis but no sharding constraint "
                    "splits the sequence dim — activations are unanchored "
                    "on seq; propagation may replicate [B, S, d] residuals "
                    "(wire the models' seq_rules constrain sites)"
                ),
                snippet="seq-constraints=0",
            )
        )
    if total == 0 and expect_sharding:
        out.append(
            Finding(
                rule="GL104",
                path=f"<ir:{label}>",
                line=0,
                scope="sharding",
                message=(
                    "no sharding constraints in the lowered program on a "
                    ">1-device mesh — GSPMD has no anchors; intermediate "
                    "layouts are left entirely to sharding propagation"
                ),
                snippet="sharding-constraints=0",
            )
        )
    else:
        detail = ", ".join(f"{k}={v}" for k, v in sorted(counts.items())) or "none"
        if seq_axis:
            detail += f", seq-dim={seq_total}"
        out.append(
            Finding(
                rule="GL104",
                path=f"<ir:{label}>",
                line=0,
                scope="sharding",
                message=f"sharding-constraint coverage per region: {detail} (total {total})",
                severity=INFO,
                snippet=f"coverage total={total}",
            )
        )
    return out


_A2A_LINE_RE = re.compile(r"= (?:\([^)]*\)|\S+) all-to-all(?:-start)?\(")
_CPERM_LINE_RE = re.compile(
    r"= (?:\([^)]*\)|\S+) collective-permute(?:-start)?\(")


def _ir_a2a_scope(hlo, label) -> list[Finding]:
    """GL105: point-to-point collectives outside sanctioned named scopes.

    The comms census (profile_step.collective_byte_census) and the region
    rollups attribute traffic by named-scope tag; a
    collective issued outside a sanctioned scope lands in ``non_moe``
    where the --aot-bytes golden never gates it. Two opcodes are policed:
    ``all-to-all`` (sanctioned: ``attn_ulysses_a2a``) and, since the
    ring/pipeline axes (r22), ``collective-permute`` (sanctioned:
    ``attn_ring_ppermute``, ``pp_stage_shift``). All-gather is NOT
    policed — GSPMD's FSDP weight gathers are legitimately everywhere —
    but the ring's dense fallback tags its gathers ``attn_ring_allgather``
    so they classify. -done halves are skipped (same instruction, counted
    once at -start or the sync op).
    """
    out: list[Finding] = []
    seen: set[str] = set()
    policed = (("all-to-all", _A2A_LINE_RE, A2A_SCOPE_RE,
                "jax.named_scope('attn_ulysses_a2a')"),
               ("collective-permute", _CPERM_LINE_RE, CPERM_SCOPE_RE,
                "jax.named_scope('attn_ring_ppermute'/'pp_stage_shift')"))
    for line in hlo.splitlines():
        for opcode, line_re, scope_re, hint in policed:
            if not line_re.search(line):
                continue
            op = re.search(r'op_name="([^"]+)"', line)
            op_name = op.group(1) if op else ""
            if op_name and scope_re.search(op_name):
                continue
            key = f"{opcode} " + (_norm(op_name) or "<no-op_name>")
            if key in seen:
                continue
            seen.add(key)
            out.append(
                Finding(
                    rule="GL105",
                    path=f"<ir:{label}>",
                    line=0,
                    scope="a2a-scope",
                    message=(
                        f"{opcode} outside sanctioned named scopes "
                        f"(op {op_name or '<untagged>'}) — wrap the call "
                        f"site in {hint} so the comms census and region "
                        "rollups can attribute its bytes"
                    ),
                    snippet=key if opcode != "all-to-all"
                    else f"a2a {_norm(op_name) or '<no-op_name>'}",
                )
            )
    return out


def lint_lowered(
    label: str,
    lowered,
    *,
    abstract_state=None,
    bf16_regions: bool = True,
    upcast_bytes: int = 1 << 20,
    donation_slack: float = 0.01,
    expect_sharding: bool | None = None,
    seq_axis: bool = False,
) -> list[Finding]:
    """IR rules on an already-lowered jitted step (test-facing hook).

    ``seq_axis=True`` (a context>1 mesh) arms GL104's sequence-dim census:
    zero seq-splitting constraints at such a mesh is an error."""
    compiled = lowered.compile()
    hlo = compiled.as_text()
    findings: list[Finding] = []
    if abstract_state is not None:
        findings += _ir_donation(hlo, label, abstract_state, donation_slack)
    if bf16_regions:
        findings += _ir_upcast(hlo, label, upcast_bytes)
    findings += _ir_host_transfer(hlo, label)
    findings += _ir_a2a_scope(hlo, label)
    try:
        asm = lowered.compiler_ir(dialect="stablehlo").operation.get_asm(
            enable_debug_info=True
        )
    except Exception:
        asm = ""
    if asm:
        findings += _ir_sharding(asm, label, bool(expect_sharding),
                                 seq_axis=seq_axis)
    return findings


def run_ir(
    model: str = "afmoe_tiny",
    *,
    per_chip_batch: int = 2,
    seq_len: int = 64,
    precision: str = "bf16",
    upcast_bytes: int = 1 << 20,
    donation_slack: float = 0.01,
    **overrides,
) -> list[Finding]:
    """Lower the Trainer's own step program for ``model`` chiplessly and run
    the IR rules on it.

    The program is ``main.py --preset gpt2_124m --model <model>`` (the LM
    recipe: AdamW, ``fsdp`` over every device) at a short sequence, built by
    the functions the ``Trainer`` calls (``core/trainer.build_model``,
    ``build_step_program``) with shapes in the state's place. ``overrides``
    are ``Config`` fields.
    """
    sys.path.insert(0, REPO_ROOT)
    import jax
    import jax.numpy as jnp

    from pytorch_distributed_training_example_tpu.core import (
        mesh as mesh_lib, trainer as trainer_lib)
    from pytorch_distributed_training_example_tpu.utils.config import (
        from_preset)

    cfg = from_preset(
        "gpt2_124m", model=model, seq_len=seq_len, precision=precision,
        global_batch_size=per_chip_batch * jax.device_count(), **overrides)
    mesh = mesh_lib.build_mesh(cfg.mesh_config())
    program = trainer_lib.build_step_program(
        cfg, mesh, 1000, trainer_lib.build_model(cfg))
    state = program.abstract_state()
    tokens = jax.ShapeDtypeStruct((cfg.global_batch_size, seq_len), jnp.int32,
                                  sharding=program.batch_sharding)
    with mesh_lib.use_mesh(mesh):
        lowered = program.train_step.lower(
            state, {"tokens": tokens, "targets": tokens})
        return lint_lowered(
            model,
            lowered,
            abstract_state=state,
            bf16_regions=precision in ("bf16", "mixed"),
            upcast_bytes=upcast_bytes,
            donation_slack=donation_slack,
            expect_sharding=mesh.size > 1,
            seq_axis=mesh.shape.get("context", 1) > 1,
        )


# ---------------------------------------------------------------------------
# Baseline
# ---------------------------------------------------------------------------

def load_baseline(path: str = DEFAULT_BASELINE) -> dict:
    if not os.path.exists(path):
        return {"suppressions": []}
    with open(path, "r", encoding="utf-8") as f:
        return json.load(f)


def _sup_key(entry: dict) -> str:
    return "|".join(
        (
            entry.get("rule", ""),
            entry.get("path", ""),
            entry.get("scope", ""),
            _norm(entry.get("snippet", "")),
        )
    )


def split_findings(findings: list[Finding], baseline: dict):
    """-> (unbaselined, baselined, stale_suppressions)."""
    sups = {_sup_key(s): s for s in baseline.get("suppressions", [])}
    used: set[str] = set()
    unbaselined, baselined = [], []
    for f in findings:
        if f.fingerprint in sups:
            used.add(f.fingerprint)
            baselined.append(f)
        else:
            unbaselined.append(f)
    stale = [s for k, s in sups.items() if k not in used]
    return unbaselined, baselined, stale


def record_baseline(findings: list[Finding], path: str = DEFAULT_BASELINE) -> dict:
    """Refresh the baseline, preserving reviewed justifications."""
    old = load_baseline(path)
    old_by_key = {_sup_key(s): s for s in old.get("suppressions", [])}
    sups = []
    for f in findings:
        if f.severity != ERROR:
            continue
        prev = old_by_key.get(f.fingerprint)
        sups.append(
            {
                "rule": f.rule,
                "path": f.path,
                "scope": f.scope,
                "snippet": _norm(f.snippet),
                "justification": (
                    prev["justification"]
                    if prev and not prev.get("justification", "").startswith("UNREVIEWED")
                    else f"UNREVIEWED: {f.message[:100]}"
                ),
            }
        )
    doc = {
        "_comment": (
            "Reviewed graftlint suppressions. Every entry needs a one-line "
            "justification; refresh with check_regression.py --lint --record "
            "(new entries land as UNREVIEWED and must be edited by hand)."
        ),
        "suppressions": sups,
    }
    with open(path, "w", encoding="utf-8") as f:
        json.dump(doc, f, indent=2, sort_keys=False)
        f.write("\n")
    return doc


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    p = argparse.ArgumentParser(description="repo-specific two-layer linter")
    p.add_argument("--root", default=REPO_ROOT, help="tree to lint (AST layer)")
    p.add_argument("--ir", metavar="MODEL", default=None, help="run IR rules on MODEL")
    p.add_argument("--all", action="store_true", help="AST + IR on afmoe_tiny")
    p.add_argument("--no-ast", action="store_true", help="skip the AST layer")
    p.add_argument("--json", action="store_true", help="machine-readable output")
    p.add_argument("--baseline", default=DEFAULT_BASELINE)
    p.add_argument("--no-baseline", action="store_true", help="ignore the baseline")
    p.add_argument("--write-baseline", action="store_true", help="refresh suppressions")
    p.add_argument("--ir-seq-len", type=int, default=64)
    p.add_argument("--ir-batch", type=int, default=2)
    p.add_argument("--ir-precision", default="bf16")
    p.add_argument("--ir-upcast-bytes", type=int, default=1 << 20)
    args = p.parse_args(argv)

    findings: list[Finding] = []
    if not args.no_ast:
        findings += run_ast(os.path.abspath(args.root))
    ir_model = args.ir or ("afmoe_tiny" if args.all else None)
    if ir_model:
        findings += run_ir(
            ir_model,
            per_chip_batch=args.ir_batch,
            seq_len=args.ir_seq_len,
            precision=args.ir_precision,
            upcast_bytes=args.ir_upcast_bytes,
        )

    baseline = {"suppressions": []} if args.no_baseline else load_baseline(args.baseline)
    unbaselined, baselined, stale = split_findings(findings, baseline)
    gate = [f for f in unbaselined if f.severity == ERROR]

    if args.write_baseline:
        record_baseline(findings, args.baseline)
        print(f"graftlint: wrote {args.baseline}")
        return 0

    if args.json:
        print(
            json.dumps(
                {
                    "findings": [f.to_dict() for f in findings],
                    "counts": {
                        "total": len(findings),
                        "errors": sum(1 for f in findings if f.severity == ERROR),
                        "baselined": len(baselined),
                        "unbaselined_errors": len(gate),
                        "stale_suppressions": len(stale),
                    },
                    "stale_suppressions": stale,
                },
                indent=2,
            )
        )
    else:
        for f in findings:
            mark = "  [baselined]" if f in baselined else ""
            print(f.render() + mark)
        for s in stale:
            print(f"graftlint: stale suppression (code moved?): {_sup_key(s)}")
        print(
            f"graftlint: {len(findings)} finding(s), {len(baselined)} baselined, "
            f"{len(gate)} unbaselined error(s), {len(stale)} stale suppression(s)"
        )
    return 1 if gate else 0


if __name__ == "__main__":
    sys.exit(main())
