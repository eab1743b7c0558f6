"""The step's map: a compiled step's text read into names a person knows.

``compiled.as_text()`` keeps, on every instruction, the scope path that jax
built while it traced the step (``metadata={op_name="jit(train_step)/
transpose(jvp(GPT2))/block_3/mlp/..."}``). That path says two things the
profile's ``fusion.1234`` does not: which part of the program the operation
belongs to (the ``jax.named_scope`` and module names, ``SCOPES``), and in
which pass of the step it runs:

- ``forward``: under ``jvp(Model)``;
- ``backward``: under ``transpose(jvp(Model))``;
- ``recompute``: a forward run again for the backward. The program asks for it
  (a block under ``nn.remat``: the path holds ``rematted_computation``;
  ``by="program"``), or the compiler does, to fit the chip's memory (XLA's
  rematerialization names its clones ``<instruction>.remat``, ``.remat2``...
  and they keep the *forward's* path; ``by="compiler"``). What the program asks
  for and the compiler's CSE then merges with the forward is gone from the
  text: what is left is what runs. CSE keeps either twin's path, so an
  operation under ``rematted_computation`` with no forward operation left at
  the same place of the source *is* the forward, run once (``forward``,
  ``by="merged"``);
- ``optimizer``: under the ``optimizer`` scope; ``other``: the rest.

``step_map(text)`` is one pass over the text; ``summary`` is what to read
first; ``write`` is what ``Trainer._stop_profile`` leaves beside a
``--profile-steps`` trace. The benchmark's readers (``chipbench/
step_passes.py``) join the same map with the device's trace. Nothing on the
start-up path imports this module, and nothing here imports jax.
"""

from __future__ import annotations

import functools
import json
import re
from typing import NamedTuple

#: Every name the program gives a part of its step: the ``jax.named_scope``
#: literals of the package (``tests/test_stepmap.py`` greps for them) and the
#: module names that readers use as scopes.
SCOPES = (
    # the six regions of every family's step (``attn`` is the module's name)
    "embed", "attn", "mlp", "norm", "head_loss", "optimizer",
    # the mixers: Mamba-2 (models/granite_hybrid.py, nemotron_h.py; the module
    # ``mamba``, ops/ssd.py), LFM2's gated convolution (models/lfm2_moe.py; the
    # module ``short_conv``) and Qwen3-Next's gated delta rule
    # (models/qwen3_next.py; the module ``gated_delta_net``, ops/gated_delta.py)
    "mamba", "in_proj", "conv1d", "ssd", "gated_norm", "out_proj",
    "short_conv", "conv_gate",
    "gated_delta_net", "conv_silu", "delta_rule", "gate_norm",
    # the expert layer (parallel/moe.py; the module ``moe`` of every expert
    # family holds a scope of the same name)
    "moe", "moe_router", "moe_dispatch", "moe_experts", "moe_shared",
    "moe_combine",
    # latent attention and the prediction module (models/glm_moe_lite.py; the
    # module ``mtp_block``)
    "mla", "mla_q", "mla_kv", "mla_rope", "mla_out",
    "mtp", "mtp_merge", "mtp_block",
    # the hyper-connections of a residual path several streams wide
    # (models/xing4.py): ``hc`` around each, its parts inside
    "hc", "hc_maps", "hc_sinkhorn", "hc_read", "hc_write",
    # the health reductions of --telemetry (utils/telemetry.py)
    "telemetry_health",
    # the collectives (ops/ring_attention.py, ops/ulysses.py,
    # parallel/pipeline.py)
    "attn_ring_ppermute", "attn_ring_allgather", "attn_ulysses_a2a",
    "pp_stage_shift",
    # the serving path (serving/)
    "serve_attn", "serve_mlp", "serve_head", "serve_cache",
)
PASSES = ("forward", "recompute", "backward", "optimizer", "other")
#: opcodes whose instruction only wraps others: a trace shows the operations
#: of their bodies by themselves, inside the wrapper's own interval
WRAPPERS = ("conditional", "while", "call")

_SCOPES = frozenset(SCOPES)
#: opcodes that never run by themselves: left out of the map
_SILENT = frozenset(("parameter", "constant", "get-tuple-element", "tuple",
                     "bitcast"))
#: what differentiation and remat put into a scope path
_PLUMBING = frozenset(("checkpoint", "rematted_computation", "closed_call"))
_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%?([\w.\-]+) = (.*)$")
_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\{$")
_OPCODE = re.compile(r"(?:^|\s)([a-z][\w\-]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"\b(?:calls|to_apply)=%?([\w.\-]+)")
_CLONE = re.compile(r"\.remat\d*(?:\.\d+)?$")
_KERNEL_TARGET = 'custom_call_target="tpu_custom_call"'


class Entry(NamedTuple):
    """One instruction of the step outside the fused computations."""

    path: str                # the ``op_name`` (of a fusion without one: its last
    #                          named instruction's); "" where the text gives none
    scopes: tuple            # its components in SCOPES, outermost first
    pass_: str               # one of PASSES
    by: str | None           # of a recompute: "program" or "compiler"; of a
    #                          forward under the recomputation's path: "merged"
    kernel: str | None       # a Pallas call's own name
    wrapper: bool            # conditional / while / call
    result: str              # the result type
    inner: frozenset | None  # of a fusion: {(innermost scope, pass)} inside

    @property
    def scope(self):
        """The innermost declared scope, or None."""
        return self.scopes[-1] if self.scopes else None

    @property
    def mixed(self):
        """A fusion that holds more than one declared innermost scope or more
        than one pass: its time is booked to its root's, whole."""
        if not self.inner:
            return False
        return (len({s for s, _ in self.inner if s is not None}) > 1
                or len({p for _, p in self.inner}) > 1)


def component(part: str) -> str:
    """``transpose(jvp(head_loss))`` -> ``head_loss``."""
    return part.rsplit("(", 1)[-1].split(")", 1)[0]


def kernel_of(path: str) -> str | None:
    """``.../ssd/jit(_bwd_call)/ssd_bwd/pallas_call`` -> ``ssd_bwd``."""
    parts = path.split("/")
    if len(parts) >= 2 and parts[-1].startswith("pallas_call"):
        return component(parts[-2])
    return None


@functools.lru_cache(maxsize=1 << 15)
def read_path(path: str):
    """``(scopes, pass, by, site)`` of a scope path alone. The site is the
    path without what differentiation and remat put into it
    (``transpose(jvp(M))/jvp(M)/checkpoint/rematted_computation/block_1/...``
    and ``jvp(M)/block_1/...`` are one site): where in the program's source
    the operation stands, whatever the pass."""
    parts = path.split("/")
    names = [component(p) for p in parts]
    scopes = tuple(c for c in names if c in _SCOPES)
    site = "/".join(c for i, c in enumerate(names) if c and c not in _PLUMBING
                    and (i == 0 or c != names[i - 1]))
    if "rematted_computation" in parts:
        return scopes, "recompute", "program", site
    if any(p.startswith("transpose(") for p in parts):
        return scopes, "backward", None, site
    if any(p.startswith("jvp(") for p in parts):
        return scopes, "forward", None, site
    return (scopes, "optimizer" if "optimizer" in scopes else "other", None,
            site)


def _read(name: str, path: str, forward_sites):
    """``(scopes, pass, by)`` of an instruction. By precedence: the compiler's
    clone by its name; the program's recomputation by its path, unless no
    forward operation of the same site is left in the text: that one *is* the
    forward, run once, which the compiler's CSE merged with its recomputation
    and left under the recomputation's path (``forward``, ``by="merged"``);
    then backward, forward, optimizer, other."""
    scopes, pass_, by, site = read_path(path)
    if _CLONE.search(name):
        return scopes, "recompute", "compiler"
    if by == "program" and site not in forward_sites:
        return scopes, "forward", "merged"
    return scopes, pass_, by


@functools.lru_cache(maxsize=2)
def step_map(text: str) -> dict:
    """``{instruction name: Entry}`` for every instruction of the text that a
    trace can show: not those inside a fused or an applied computation (what a
    fused one holds is its fusion's ``inner``), nor parameters, constants and
    the like. Cached on the text, which runs to megabytes."""
    computations, fused, forward_sites, current = {}, set(), set(), None
    for line in (text or "").splitlines():
        if not line.startswith(" "):
            found = _COMPUTATION.match(line)
            if found:
                current = computations.setdefault(found.group(1), [])
            continue
        found = _INSTRUCTION.match(line)
        if not found or current is None:
            continue
        name, rest = found.groups()
        opcode = _OPCODE.search(rest)
        result = rest[:opcode.start()] if opcode else rest.split(" ", 1)[0]
        opcode = opcode.group(1) if opcode else ""
        path = _OP_NAME.search(rest)
        path = path.group(1) if path else ""
        if path and not _CLONE.search(name):
            _, pass_, _, site = read_path(path)
            if pass_ == "forward":
                forward_sites.add(site)
        calls = _CALLS.search(rest)
        if calls:
            fused.add(calls.group(1))
        kernel = (kernel_of(path) if opcode == "custom-call"
                  and _KERNEL_TARGET in rest else None)
        current.append((name, path, opcode, result, kernel,
                        calls and calls.group(1)))
    inner = {called: frozenset(
        (scopes[-1] if scopes else None, pass_) for scopes, pass_, _ in (
            _read(name, path, forward_sites)
            for name, path, *_ in computations.get(called, ()) if path))
        for called in fused}
    out = {}
    for called, instructions in computations.items():
        if called in fused:
            continue
        for name, path, opcode, result, kernel, calls in instructions:
            if opcode in _SILENT:
                continue
            if not path and opcode == "fusion":
                # a root the compiler made (a bitcast, a convert, a tuple)
                # has no path: the fusion is its last named instruction's
                path = next((inside[1] for inside in reversed(
                    computations.get(calls, ())) if inside[1]), "")
            scopes, pass_, by = _read(name, path, forward_sites)
            out[name] = Entry(path, scopes, pass_, by, kernel,
                              opcode in WRAPPERS, result,
                              inner[calls] if opcode == "fusion" and calls
                              else None)
    return out


def summary(entries: dict) -> dict:
    """What an operator reads first: the instructions by pass, the kernels'
    calls by pass (``{"delta_rule_fwd": {"forward": 3, "recompute": 0,
    "backward": 0}}``: did the forward run twice), the compiler's clones, and
    the fusions that mix scopes or passes. Counts of the text: an instruction
    in a loop's body counts once."""
    by_pass = dict.fromkeys(PASSES, 0)
    kernels, clones, mixed = {}, 0, 0
    for entry in entries.values():
        by_pass[entry.pass_] += 1
        clones += entry.by == "compiler"
        mixed += entry.mixed
        if entry.kernel:
            calls = kernels.setdefault(entry.kernel, dict.fromkeys(
                ("forward", "recompute", "backward"), 0))
            calls[entry.pass_] = calls.get(entry.pass_, 0) + 1
    return {"instructions": by_pass, "kernel_calls": dict(sorted(
        kernels.items())), "compiler_clones": clones, "mixed_fusions": mixed}


def write(text: str, path: str) -> dict:
    """``step_map.json`` at ``path`` (instruction -> path, scopes, pass, by,
    kernel, wrapper, result, inner); returns the map's ``summary``."""
    entries = step_map.__wrapped__(text)   # a training run keeps no 20 MB text
    with open(path, "w") as fh:
        json.dump({name: {
            "path": e.path, "scopes": e.scopes, "pass": e.pass_, "by": e.by,
            "kernel": e.kernel, "wrapper": e.wrapper, "result": e.result,
            "inner": None if e.inner is None else sorted(
                e.inner, key=lambda pair: (pair[0] or "", pair[1]))}
            for name, e in entries.items()}, fh)
    return summary(entries)
