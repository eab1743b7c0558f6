#!/usr/bin/env python
"""Did an edit leave a benchmark cell's compiled step as it was? Compiles each
cell's step for a described v5e in the tree it is run from (no chip: the TPU
compiler is installed), writes the step's text without source positions, and
compares two such directories.

    cd <tree> && JAX_PLATFORMS=cpu python <this file> --out /root/scratch/a [--cells a,b]
    python <this file> --compare /root/scratch/a /root/scratch/b

The step is built as the benchmark's driver builds it (the cell's
configuration and traffic files, ``from_preset``, ``build_step_program``) on
shapes, as ``tests/test_chip_compile._share_step`` does; ``held`` on each row
is the compiled step's arguments + temporaries + unaliased outputs, the chip's
``step_mem_gb`` to the byte. Source positions are what differs between two
trees whose programs are equal: ``source_file`` / ``source_line`` /
``stack_frame_id`` on every instruction, the numbered tables at the head of the
text, and the locations inside each Mosaic kernel's serialized body, which is
replaced by the hash of its location-free assembly. 30-90 s a cell; one
process at a time may hold libtpu here. PR 54 showed the eight accepted
cells' steps unchanged this way (PERF.md section 6).
"""

import argparse
import base64
import hashlib
import json
import os
import re
import sys
import time


def _asm(body: str) -> str:
    from jax._src.interpreters import mlir as jax_mlir
    from jaxlib.mlir import ir

    with jax_mlir.make_ir_context() as ctx:
        ctx.allow_unregistered_dialects = True
        module = ir.Module.parse(base64.b64decode(body))
        return module.operation.get_asm(enable_debug_info=False)


def without_positions(text: str) -> str:
    """``text`` with everything taken out that says where in a source file an
    instruction came from."""
    text = re.sub(r'"body": ?"([A-Za-z0-9+/=]+)"', lambda m: '"body_sha":"%s"'
                  % hashlib.sha256(_asm(m.group(1)).encode()).hexdigest(),
                  text)
    text = re.sub(r' (?:stack_frame_id|source_line|source_end_line|'
                  r'source_column|source_end_column)=\d+', "", text)
    text = re.sub(r' source_file="[^"]*"', "", text)
    # the tables of files, functions, positions and frames: numbered rows
    return "\n".join(line for line in text.splitlines()
                     if not re.match(r"^\d+ ", line))


def compile_cell(name: str, manifest: dict):
    """``(text without positions, held bytes)`` of the cell's step."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, PartitionSpec as P

    from pytorch_distributed_training_example_tpu.core import (
        mesh as mesh_lib, train_loop, trainer as trainer_lib)
    from pytorch_distributed_training_example_tpu.core.train_state import (
        TrainState)
    from pytorch_distributed_training_example_tpu.ops import backend
    from pytorch_distributed_training_example_tpu.utils.config import (
        from_preset)

    backend.on_tpu = lambda: True   # as tests/test_chip_compile.as_tpu
    device = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0]
    cell = next(w for w in manifest["workloads"] if w["name"] == name)
    with open(f"chipbench/configs/{cell['config']}.json") as fh:
        config = json.load(fh)
    with open(f"chipbench/traffic/{cell['traffic']}.json") as fh:
        traffic = json.load(fh)
    cfg = from_preset(config["preset"], **{
        **config["overrides"], **traffic.get("overrides", {}), "seed": 0})
    mesh = mesh_lib.build_mesh(dict(data=1, fsdp=1), devices=[device])
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
    state = jax.tree.map(lambda s, sh: jax.ShapeDtypeStruct(
        s.shape, s.dtype, sharding=sh), shape, shardings)
    rows = NamedSharding(mesh, P(("data", "fsdp")))
    batch = {k: jax.ShapeDtypeStruct((cfg.global_batch_size, cfg.seq_len),
                                     jnp.int32, sharding=rows)
             for k in ("tokens", "targets")}
    with mesh_lib.use_mesh(mesh):
        compiled = program.train_step.lower(state, batch).compile()
    mem = compiled.memory_analysis()
    held = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    text = without_positions(compiled.as_text())
    jax.clear_caches()
    return text, held


def compare(a: str, b: str) -> int:
    differing = 0
    for name in sorted(os.listdir(a)):
        other = os.path.join(b, name)
        if not name.endswith(".hlo") or not os.path.exists(other):
            continue
        with open(os.path.join(a, name)) as fa, open(other) as fb:
            x, y = fa.read(), fb.read()
        row = {"cell": name[:-4], "same": x == y, "bytes": [len(x), len(y)]}
        if x != y:
            differing += 1
            pairs = list(zip(x.splitlines(), y.splitlines()))
            row["lines_differing"] = sum(p != q for p, q in pairs)
            p, q = next((p, q) for p, q in pairs if p != q)
            at = next(i for i, (c, d) in enumerate(zip(p, q)) if c != d)
            row["first"] = [p[max(0, at - 120):at + 120],
                            q[max(0, at - 120):at + 120]]
        print(json.dumps(row), flush=True)
    return differing


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--out", help="directory for <cell>.hlo of this tree")
    p.add_argument("--cells", help="comma-separated; default: every "
                   "language-model cell of BENCHMARK.json")
    p.add_argument("--compare", nargs=2, metavar=("A", "B"))
    args = p.parse_args(argv)
    if args.compare:
        return 1 if compare(*args.compare) else 0
    sys.path.insert(0, os.getcwd())
    with open("BENCHMARK.json") as fh:
        manifest = json.load(fh)
    cells = (args.cells.split(",") if args.cells
             else [w["name"] for w in manifest["workloads"]])
    os.makedirs(args.out, exist_ok=True)
    for name in cells:
        t0 = time.time()
        text, held = compile_cell(name, manifest)
        with open(os.path.join(args.out, name + ".hlo"), "w") as fh:
            fh.write(text)
        print(json.dumps({
            "cell": name, "held": held, "bytes": len(text),
            "sha": hashlib.sha256(text.encode()).hexdigest(),
            "seconds": round(time.time() - t0, 1)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
