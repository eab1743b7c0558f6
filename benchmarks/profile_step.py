"""Readers of a compiled step's HLO text and of an xplane trace.

What other programs import: ``build_op_categories`` (every fusion classified
by what its called computation actually contains: convolution > dot >
scatter > reduce > elementwise, first match wins; no name-guessing),
``build_op_moe_tags`` / ``build_op_moe_weights`` / ``build_pallas_interior``
(the ``moe_*`` named scopes), ``build_op_bytes`` (unique operand + result
buffer bytes per executed op), ``collective_byte_census`` and ``collect_ops``
(device time per XLA op out of a ``jax.profiler`` dump). Used by
``serve_bench.py``, ``flash_micro.py``, ``ssd_micro.py``,
``moe_rows_micro.py``, ``graftlint.py`` and
``tests/test_bench_regression.py``. The cells' per-region and
per-kernel split is ``chipbench/run.py --trace 1``, not this file.
"""

from __future__ import annotations

import collections
import glob
import re
import sys

# Category priority (first present wins) for a fused computation's body.
_PRIORITY = ["attention_kernel", "conv", "matmul", "scatter", "gather",
             "pool", "reduce"]


def _body_category(body: str) -> str:
    found = set()
    for line in body.splitlines():
        if "tpu_custom_call" in line or "mosaic" in line:
            found.add("attention_kernel")
        elif " convolution(" in line:
            # XLA:TPU lowers big dot_generals to convolution instructions;
            # the source metadata tells them apart from real convs.
            found.add("matmul" if "dot_general" in line else "conv")
        elif " dot(" in line:
            found.add("matmul")
        elif " scatter(" in line:
            found.add("scatter")
        elif " gather(" in line:
            found.add("gather")
        elif " reduce-window(" in line:
            found.add("pool")
        elif " reduce(" in line:
            found.add("reduce")
    for cat in _PRIORITY:
        if cat in found:
            return cat
    return "elementwise"


def _src_tag(line: str) -> str | None:
    """Short source tag from metadata: last path components of op_name."""
    m = re.search(r'op_name="([^"]+)"', line)
    if not m:
        return None
    return "/".join(m.group(1).split("/")[-3:])


def build_op_categories(hlo_text: str):
    """Map every instruction name -> category using computation contents."""
    # Split into computations: "%name (args) -> ret {\n ... \n}"
    comp_bodies = {}
    for m in re.finditer(r"^(?:ENTRY )?%?([\w.\-]+)(?:\.clone)? \([^)]*\) -> .*? \{\n(.*?)^\}",
                         hlo_text, re.M | re.S):
        comp_bodies[m.group(1)] = m.group(2)
    comp_cat = {name: _body_category(body)
                for name, body in comp_bodies.items()}

    op_cat = {}
    op_src = {}
    for name, body in comp_bodies.items():
        for line in body.splitlines():
            # Result shapes may be tuples with spaces and one level of
            # nested parens from layouts (T(8,128), S(1)); the opcode is
            # the first lowercase token directly before a '(' after '='.
            im = re.match(
                r"\s+(?:ROOT )?%?([\w.\-]+) = .*?([a-z][a-z0-9\-]*)\(", line)
            if not im:
                continue
            op, opcode = im.group(1), im.group(2)
            src = _src_tag(line)
            if src:
                op_src[op] = src
            if opcode == "fusion":
                cm = re.search(r"calls=%?([\w.\-]+)", line)
                op_cat[op] = comp_cat.get(cm.group(1), "elementwise") \
                    if cm else "elementwise"
            elif opcode == "custom-call":
                op_cat[op] = ("attention_kernel"
                              if "tpu_custom_call" in line else "custom_call")
            elif opcode == "convolution":
                op_cat[op] = "matmul" if "dot_general" in line else "conv"
            elif opcode == "dot":
                op_cat[op] = "matmul"
            elif opcode in ("all-reduce", "all-gather", "reduce-scatter",
                            "all-to-all", "collective-permute"):
                op_cat[op] = "collective"
            elif opcode.startswith("copy") or opcode in ("bitcast", "convert",
                                                         "transpose", "reshape"):
                op_cat[op] = "copy_layout"
            else:
                op_cat[op] = opcode
    return op_cat, op_src


# MoE step regions tagged with jax.named_scope in parallel/moe.py. The tag
# survives into op_name metadata for forward ops ("...moe_dispatch/...") and
# for their cotangents (jax keeps the scope path inside transpose(...)), so
# a rollup by tag attributes fwd+bwd time per region. The dropless kernel
# (ops/grouped_matmul.py) tags its pallas calls moe_experts_gmm; nested
# under moe_experts the leftmost match wins (bytes stay comparable across
# dispatch impls), while kernel ops whose scope stack XLA rewrote down to
# the inner tag still classify instead of leaking into non_moe.
_MOE_TAG_RE = re.compile(
    r"\bmoe_(router|dispatch|experts_gmm|experts|combine|aux)\b")


def _moe_tag(line: str, tag_re: re.Pattern | None = None) -> str | None:
    """Region tag of one HLO line. Default: the MoE named-scope tags.
    ``tag_re`` swaps in another scope-tag alphabet (e.g. the serve_* tags
    of the decode step — benchmarks/serve_bench.py) and attributes by the
    full match text."""
    m = re.search(r'op_name="([^"]+)"', line)
    if not m:
        return None
    if tag_re is not None:
        t = tag_re.search(m.group(1))
        return t.group(0) if t else None
    t = _MOE_TAG_RE.search(m.group(1))
    return f"moe_{t.group(1)}" if t else None


def build_op_moe_tags(hlo_text: str, tag_re: re.Pattern | None = None):
    """Map instruction name -> MoE step region (moe_router / moe_dispatch /
    moe_experts / moe_combine / moe_aux) from the named-scope tags in
    op_name metadata. A fusion is attributed to the tag the majority of its
    fused instructions carry (mixed fusions happen at region boundaries);
    untagged instructions are absent from the map."""
    comp_bodies = {}
    for m in re.finditer(r"^(?:ENTRY )?%?([\w.\-]+)(?:\.clone)? \([^)]*\) -> .*? \{\n(.*?)^\}",
                         hlo_text, re.M | re.S):
        comp_bodies[m.group(1)] = m.group(2)
    comp_tags: dict[str, collections.Counter] = {}
    for name, body in comp_bodies.items():
        c = collections.Counter()
        for line in body.splitlines():
            t = _moe_tag(line, tag_re)
            if t:
                c[t] += 1
        comp_tags[name] = c

    op_moe = {}
    for name, body in comp_bodies.items():
        for line in body.splitlines():
            im = re.match(
                r"\s+(?:ROOT )?%?([\w.\-]+) = .*?([a-z][a-z0-9\-]*)\(", line)
            if not im:
                continue
            op, opcode = im.group(1), im.group(2)
            tag = _moe_tag(line, tag_re)
            if opcode == "fusion":
                cm = re.search(r"calls=%?([\w.\-]+)", line)
                cnt = comp_tags.get(cm.group(1)) if cm else None
                if cnt:
                    tag = cnt.most_common(1)[0][0]
            if tag:
                op_moe[op] = tag
    return op_moe


def build_op_moe_weights(hlo_text: str, tag_re: re.Pattern | None = None):
    """Map instruction name -> {region: fraction} for PROPORTIONAL byte
    attribution of mixed fusions.

    ``build_op_moe_tags`` is winner-take-all: a fusion goes to whichever
    region tags the most interior lines. That is right for the trace-timing
    path (a timed event is indivisible) but wrong for byte accounting on
    XLA:CPU, which builds whole-block backward mega-fusions (~900
    instructions) where a handful of tagged lines — e.g. 24 moe_router
    [T,d] cotangent converts vs 12 moe_dispatch lines, 96% untagged —
    decided the winner and charged the entire fusion's boundary traffic to
    one region (r7 recorded 125 GB of "router" bytes this way; the genuine
    router share is ~2.3x smaller).

    Here each fusion's bytes are split by the RESULT bytes of its tagged
    interior lines over all non-view interior result bytes; the untagged
    remainder stays unattributed (the caller charges it to non_moe).
    Fusions whose interior carries tags but zero bytes (scalar reducers)
    fall back to line majority. Non-fusion tagged instructions keep their
    own tag at weight 1.0. Fractions for an op sum to <= 1."""
    comp_bodies = {}
    for m in re.finditer(r"^(?:ENTRY )?%?([\w.\-]+)(?:\.clone)? \([^)]*\) -> .*? \{\n(.*?)^\}",
                         hlo_text, re.M | re.S):
        comp_bodies[m.group(1)] = m.group(2)
    line_re = re.compile(
        r"\s+(?:ROOT )?%?([\w.\-]+) = (.*?)([a-z][a-z0-9\-]*)\(")

    comp_frac: dict[str, dict[str, float]] = {}
    for name, body in comp_bodies.items():
        tag_bytes: collections.Counter = collections.Counter()
        tag_lines: collections.Counter = collections.Counter()
        total = 0
        for line in body.splitlines():
            im = line_re.match(line)
            if not im or im.group(3) in _VIEW_OPS:
                continue
            b = sum(_shape_bytes(dt, dims)
                    for dt, dims, _ in _SHAPE_LAYOUT_RE.findall(im.group(2)))
            total += b
            t = _moe_tag(line, tag_re)
            if t:
                tag_bytes[t] += b
                tag_lines[t] += 1
        if total:
            comp_frac[name] = {t: b / total for t, b in tag_bytes.items()}
        elif tag_lines:
            comp_frac[name] = {tag_lines.most_common(1)[0][0]: 1.0}

    op_w: dict[str, dict[str, float]] = {}
    for name, body in comp_bodies.items():
        for line in body.splitlines():
            im = line_re.match(line)
            if not im:
                continue
            op, opcode = im.group(1), im.group(3)
            if opcode == "fusion":
                cm = re.search(r"calls=%?([\w.\-]+)", line)
                w = comp_frac.get(cm.group(1)) if cm else None
                if w:
                    op_w[op] = w
                    continue
            t = _moe_tag(line, tag_re)
            if t:
                op_w[op] = {t: 1.0}
    return op_w


# Interpret-mode Pallas emulation: off-TPU, pallas_call lowers to an XLA
# while loop that walks the kernel grid, materializing every VMEM block
# move as a full-array dynamic-slice / dynamic-update-slice per grid step.
# On the real target the kernel is ONE custom call whose HBM traffic is
# its operands + results; the loop interior is pure CPU-lowering artifact
# (r14: it charged ~103 GB of phantom traffic to moe_experts for the
# dropless grouped matmul at a bench shape that is gone). Interior ops
# carry the kernel's named scope followed by the loop path in op_name
# ("...moe_experts_gmm/while/body/..."); the while instruction itself
# (scope path ends at .../while) is KEPT — its carried tuple is the
# operand+result boundary, i.e. what a real custom call would be charged.
# Deliberately scoped to the dropless grouped-matmul kernel tag so rows
# recorded for non-Pallas impls are byte-identical under this rule.
_PALLAS_INTERIOR_RE = re.compile(r"\bmoe_experts_gmm/while/")


def build_pallas_interior(hlo_text: str):
    """Instruction names interior to an interpret-mode Pallas grid loop
    (``_PALLAS_INTERIOR_RE`` on op_name). A byte report drops them from
    its tabulation entirely — they do not exist on the target."""
    interior = set()
    for line in hlo_text.splitlines():
        m = re.match(r"\s+(?:ROOT )?%?([\w.\-]+) = ", line)
        if not m:
            continue
        nm = re.search(r'op_name="([^"]+)"', line)
        if nm and _PALLAS_INTERIOR_RE.search(nm.group(1)):
            interior.add(m.group(1))
    return interior


_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2,
                "bf16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
                "f64": 8, "f8e4m3fn": 1, "f8e5m2": 1}

_DTYPE_PAT = (r"(?:pred|[us](?:8|16|32|64)|bf16|f(?:16|32|64)|"
              r"f8e4m3fn|f8e5m2)")
_SHAPE_RE = re.compile(rf"\b({_DTYPE_PAT})\[([\d,]*)\]")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


# Zero-cost view/bookkeeping opcodes: no data movement of their own, and
# their results alias other buffers — counting them double-counts.
_VIEW_OPS = {"parameter", "constant", "get-tuple-element", "tuple", "bitcast"}

# Layout-aware shape: dims + optional {layout}; "S(<n>)" in the layout marks
# a buffer assigned to alternate memory space n (VMEM on TPU) — it never
# touches HBM. r4's accounting missed both directions here (ADVICE r4 +
# r5 re-derivation): operand lists in this XLA's as_text() are bare
# "%name" references (no inline shapes), so reads parsed as zero, while
# result bytes were counted even for views and VMEM-resident buffers.
_SHAPE_LAYOUT_RE = re.compile(rf"\b({_DTYPE_PAT})\[([\d,]*)\](\{{[^}}]*\}})?")


def build_op_bytes(hlo_text: str):
    """Per-instruction HBM traffic model from the scheduled module.

    Two passes. First, every instruction's name is mapped to its result
    buffer size (HBM portion only — tuple components whose layout carries
    an ``S(n)`` alternate-memory-space tag are excluded). Then each
    instruction is charged:

    - view/bookkeeping ops (parameter, constant, get-tuple-element, tuple,
      bitcast): 0 bytes;
    - ``*-start`` async halves: 0 (the transfer is charged to ``*-done``
      so a DMA is counted once, not twice);
    - everything else: its HBM result bytes (written) plus, for each
      UNIQUE operand name, that operand's HBM result bytes (read) — a
      buffer lookup, because operands appear as bare ``%name`` references.

    Unlike XLA's cost-model "bytes accessed" (which double-counts fused
    interior uses and can exceed physical bandwidth)
    this approximates the DMA traffic the scheduled program issues. It is
    still a model: a tiled conv may re-read inputs (undercount) and a
    consumer whose producer stayed VMEM-resident is overcounted; the
    physical-peak sanity check lives with the caller's roofline."""
    line_re = re.compile(
        r"^\s+(?:ROOT )?%?([\w.\-]+) = (.*?)([a-z][a-z0-9\-]*)\((.*)$", re.M)
    info: dict[str, tuple[str, int, list[str]]] = {}
    for m in line_re.finditer(hlo_text):
        op, result_txt, opcode, rest = m.groups()
        # operands end where attributes begin
        for cut in (", kind=", ", calls=", ", metadata=", ", backend_config=",
                    ", custom_call_target=", ", dimensions=", ", window=",
                    ", to_apply=", ", condition=", ", body=", ", select=",
                    ", scatter=", ", control-predecessors=", ", sharding=",
                    ", frontend_attributes="):
            idx = rest.find(cut)
            if idx != -1:
                rest = rest[:idx]
        out_b = 0
        for dt, dims, layout in _SHAPE_LAYOUT_RE.findall(result_txt):
            if "S(" in (layout or ""):
                continue  # alternate memory space: not HBM traffic
            out_b += _shape_bytes(dt, dims)
        operands = re.findall(r"%([\w.\-]+)", rest)
        if not operands:
            # Some XLA versions print bare operand names without '%'
            # (the ADVICE-r4 fragility); fall back to comma-split tokens —
            # the caller filters them against the instruction map, which
            # rejects shape/attribute fragments.
            operands = [t.strip().split(" ")[-1].strip("()")
                        for t in rest.split(",") if t.strip()]
        info[op] = (opcode, out_b, operands)

    op_bytes = {}
    total_in = total_out = 0
    for op, (opcode, out_b, operands) in info.items():
        if opcode in _VIEW_OPS or opcode.endswith("-start"):
            op_bytes[op] = 0
            continue
        if opcode.endswith("-done"):
            op_bytes[op] = out_b  # one side of the DMA, counted once
            total_out += out_b
            continue
        in_b = 0
        seen = set()
        for name in operands:
            if name in seen:
                continue
            seen.add(name)
            oi = info.get(name)
            if oi is not None:
                in_b += oi[1]
        op_bytes[op] = in_b + out_b
        total_in += in_b
        total_out += out_b
    if total_out and total_in < 0.2 * total_out:
        # Reads should be comparable to writes across a module; a tiny
        # read term means the operand parse missed this dump's format and
        # the roofline is underreporting HBM traffic.
        print(f"WARNING: parsed operand-read bytes ({total_in/1e9:.2f} GB) "
              f"implausibly small vs result bytes ({total_out/1e9:.2f} GB) "
              "— HLO operand format likely unmatched; measured roofline "
              "will underreport traffic", file=sys.stderr)
    return op_bytes


# EP comms census: the collective opcodes whose result buffers carry the
# MoE transport cost (r17 ep_dispatch A/B). async -start halves are skipped
# and the transfer charged once at -done, matching build_op_bytes.
_COLLECTIVE_RE = re.compile(
    r"^(all-to-all|all-gather|all-reduce|reduce-scatter|collective-permute)"
    r"(-start|-done)?$")


def collective_byte_census(hlo_text: str):
    """Per-opcode / per-region byte census of the collectives in a compiled
    module: a chipless comms model.

    Every collective instruction is charged its HBM result-buffer bytes
    (``_shape_bytes`` over the printed result shape, alternate-memory
    components excluded) — a per-device transfer-volume proxy, not a wire
    model: an all-gather's result is the fully gathered buffer each device
    materializes, an all-to-all's is the shards it receives. That is the
    quantity the replicated-vs-a2a dropless decision trades (weight gathers
    vs token shards), so the rows are comparable across ``ep_dispatch``
    modes lowered at the same mesh. Attribution to MoE regions reuses the
    named-scope tags (``_moe_tag``); untagged collectives (grad psum over
    data axes, ...) land in ``non_moe``.

    Returns ``{"total_bytes", "moe_bytes", "by_opcode": {opcode: {"count",
    "bytes"}}, "by_region": {region: {"count", "bytes"}}}``. Counts are
    instruction-level (a collective inside a while body counts once).
    """
    line_re = re.compile(
        r"^\s+(?:ROOT )?%?([\w.\-]+) = (.*?)([a-z][a-z0-9\-]*)\(", re.M)
    by_opcode: dict[str, dict] = {}
    by_region: dict[str, dict] = {}
    total = moe = 0
    for m in line_re.finditer(hlo_text):
        _, result_txt, opcode = m.groups()
        cm = _COLLECTIVE_RE.match(opcode)
        if not cm or cm.group(2) == "-start":
            continue
        base = cm.group(1)
        b = 0
        for dt, dims, layout in _SHAPE_LAYOUT_RE.findall(result_txt):
            if "S(" in (layout or ""):
                continue
            b += _shape_bytes(dt, dims)
        line = hlo_text[m.start():hlo_text.find("\n", m.start())]
        region = _moe_tag(line) or "non_moe"
        o = by_opcode.setdefault(base, {"count": 0, "bytes": 0})
        o["count"] += 1
        o["bytes"] += b
        r = by_region.setdefault(region, {"count": 0, "bytes": 0})
        r["count"] += 1
        r["bytes"] += b
        total += b
        if region != "non_moe":
            moe += b
    return {"total_bytes": total, "moe_bytes": moe,
            "by_opcode": dict(sorted(by_opcode.items())),
            "by_region": dict(sorted(by_region.items(),
                                     key=lambda kv: -kv[1]["bytes"]))}


def collect_ops(trace_dir: str):
    """Aggregate XLA-op events across all device planes/steps in the dump."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(trace_dir + "/**/*.xplane.pb", recursive=True))
    if not paths:
        raise FileNotFoundError(f"no xplane.pb under {trace_dir}")
    ops = collections.defaultdict(lambda: [0.0, 0])  # name -> [ns, count]
    module_ns = 0.0
    module_runs = 0
    for path in paths:
        pd = ProfileData.from_file(path)
        for plane in pd.planes:
            if not plane.name.startswith("/device:"):
                continue
            for line in plane.lines:
                if line.name == "XLA Modules":
                    for ev in line.events:
                        module_ns += ev.duration_ns
                        module_runs += 1
                if line.name != "XLA Ops":
                    continue
                for ev in line.events:
                    rec = ops[ev.name]
                    rec[0] += ev.duration_ns
                    rec[1] += 1
    return ops, module_ns, module_runs
