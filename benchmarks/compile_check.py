#!/usr/bin/env python3
"""Compile a cell's programs at published widths for a described v5e.

    python3 benchmarks/compile_check.py --workload <name> [--set key=value]

Costs no chip time. The TPU compiler is installed in the sandbox and
compiles for a chip that is described, not attached
(``jax.experimental.topologies``, ``v5e:2x2``). The cell is built on the
CPU at its real sizes through its model file; then the step (training)
or every prefill bucket and the decode step (serving) is lowered from
shapes placed on the described chip(s). What the chip's compiler would
refuse -- a program that does not fit, a kernel Mosaic rejects, a mesh
rule -- it refuses here. Prints each program's ``memory_analysis`` and
the collectives and custom calls in its HLO. A compile that passes is
not a chip run: nothing executes and no time is measured.

``--set batch=8`` overrides a key of the traffic file and
``--set pool.max_seqs=40`` one inside a group, to size a job before the
file is written. This reaches into the trainer's and the predictor's
private members, which is why it is a rehearsal tool and not the
harness.
"""
from __future__ import annotations

import argparse
import json
import os
import re
import sys
import time

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("TPU_LOG_DIR", "disabled")
# an executable compiled for a described chip cannot be read back without
# one: keep these out of the checkout's compile cache
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "0"
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def report(label, compiled, seconds):
    text = compiled.as_text()
    ma = compiled.memory_analysis()
    gb = 1e9
    peak = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    ops = {}
    for name in re.findall(r"[}\])] (all-reduce(?:-start)?|all-gather"
                           r"(?:-start)?|reduce-scatter|all-to-all|"
                           r"collective-permute(?:-start)?|custom-call)\(",
                           text):
        ops[name] = ops.get(name, 0) + 1
    targets = sorted(set(re.findall(r'custom_call_target="([^"]+)"', text)))
    print(f"{label}: compiled in {seconds:.1f} s; arguments "
          f"{ma.argument_size_in_bytes / gb:.3f} GB, outputs "
          f"{ma.output_size_in_bytes / gb:.3f} GB, aliased "
          f"{ma.alias_size_in_bytes / gb:.3f} GB, temporaries "
          f"{ma.temp_size_in_bytes / gb:.3f} GB -> peak "
          f"{peak / gb:.3f} GB per chip; HLO ops {ops}; custom-call "
          f"targets {targets}", flush=True)
    return peak


def shapes_on(tree, sharding_of):
    import jax

    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                       sharding=sharding_of(a)), tree)


def check_training(cell, topo):
    import jax
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from benchmarks.harness import manifest

    model = manifest.module("models", cell.config["model"])
    reference = manifest.module("references", cell.config["reference"])
    cpu_mesh_traffic = dict(cell.traffic, mesh={"dp": 1})
    # the net is built on the CPU, where the eager forward that fixes the
    # deferred shapes cannot run a Pallas kernel: build it dense, then
    # give the blocks the attention the configuration states, which the
    # step picks up when it is traced for the chip
    train = cell.config["train"]
    impl = train.get("attention_impl", "dense")
    job = model.build_trainer(
        dict(cell.config, train=dict(train, attention_impl="dense")),
        cpu_mesh_traffic, 0, jax.devices()[:1], reference)
    if impl != "dense":
        for blk in job.net.blocks:
            getattr(blk, "block", blk).attn._impl = impl
    tr = job.trainer
    mesh = Mesh(np.asarray(topo.devices[:cell.chips]), ("dp",))
    repl = NamedSharding(mesh, PartitionSpec())
    rows = NamedSharding(mesh, PartitionSpec("dp"))
    compute_loss, update = tr._make_compute_loss(), tr._update

    def step(params, aux, opt_state, x, y):
        (loss, new_aux), grads = jax.value_and_grad(
            compute_loss, has_aux=True)(params, aux, x, y)
        new_params, new_opt = update(params, grads, opt_state)
        return new_params, new_aux, new_opt, loss

    ring = jax.eval_shape(lambda: job.make_ring(0, 1))[0]
    x, y = (jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=rows)
            for a in ring)
    state = shapes_on((tr.params, tr.aux, tr.opt_state), lambda a: repl)
    t0 = time.perf_counter()
    compiled = jax.jit(step, donate_argnums=(0, 1, 2)).lower(
        *state, x, y).compile()
    peak = report(f"{cell.name} step, batch {cell.traffic['batch']}",
                  compiled, time.perf_counter() - t0)
    held = sum(int(np.prod(p.shape)) * 4
               for p in job.net.collect_params().values())
    grads = sum(int(np.prod(p.shape)) * 4
                for p in job.net.collect_params().values()
                if p.grad_req != "null")
    n_ring = manifest.module("loops", cell.traffic["loop"]).ring_of(
        cell.traffic)
    print(f"  beside the step the gluon net keeps {held / 1e9:.3f} GB of "
          f"parameters and {grads / 1e9:.3f} GB of gradient buffers on chip "
          f"0, and the ring of {n_ring} batches "
          f"{n_ring * sum(int(np.prod(a.shape)) * a.dtype.itemsize for a in ring) / cell.chips / 1e9:.3f}"
          f" GB per chip: about {(peak + held + grads) / 1e9:.2f} GB in all")

    # the check's two programs: the forward pass under the training
    # policy and the float32 reference, both after the window
    from mxnet_tpu import parallel

    positions = None
    if job.positions:
        picked = job.positions(0)
        positions = jax.ShapeDtypeStruct(picked.shape, picked.dtype,
                                         sharding=rows)
    t0 = time.perf_counter()
    compiled = jax.jit(job.policy_forward()).lower(
        *shapes_on((job.initial_params(), parallel.aux_arrays(job.net)),
                   lambda a: repl), x, positions).compile()
    report(f"{cell.name} forward pass under the training policy", compiled,
           time.perf_counter() - t0)
    weights = shapes_on(job.reference_weights(), lambda a: repl)
    t0 = time.perf_counter()
    compiled = jax.jit(job.reference_fn).lower(weights, x, y,
                                               positions).compile()
    report(f"{cell.name} float32 reference loss and logits", compiled,
           time.perf_counter() - t0)


def check_serving(cell, topo):
    import jax
    import numpy as np
    from jax.sharding import SingleDeviceSharding

    from benchmarks.harness import manifest

    model = manifest.module("models", cell.config["model"])
    reference = manifest.module("references", cell.config["reference"])
    job = model.build_server(cell.config, cell.traffic, 0, jax.devices()[:1],
                             reference)
    pred = job.predictor
    chip = SingleDeviceSharding(topo.devices[0])

    def on_chip(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    kv = shapes_on(tuple(pred._kv), lambda a: chip)
    params = shapes_on(pred._param_vals(), lambda a: chip)
    held = sum(int(np.prod(a.shape)) * a.dtype.itemsize
               for a in list(pred._kv) + list(pred._param_vals()))
    print(f"{cell.name}: K/V pool {pred.kv_hbm_bytes / 1e9:.3f} GB + "
          f"weights = {held / 1e9:.3f} GB resident", flush=True)
    i32 = np.int32
    for bucket in pred.prefill_buckets:
        fn = pred._build_exec(("prefill", bucket))._fn
        t0 = time.perf_counter()
        compiled = jax.jit(fn, donate_argnums=(3, 4, 5, 6)).lower(
            on_chip((1, bucket), i32), on_chip((1,), i32),
            on_chip((pred.max_pages,), i32), *kv, *params).compile()
        report(f"{cell.name} prefill bucket {bucket}", compiled,
               time.perf_counter() - t0)
    fn = pred._build_exec(("step",))._fn
    rows = on_chip((pred.max_seqs,), i32)
    t0 = time.perf_counter()
    compiled = jax.jit(fn, donate_argnums=(4, 5, 6, 7)).lower(
        rows, rows, rows, on_chip((pred.max_seqs, pred.max_pages), i32),
        *kv, *params).compile()
    report(f"{cell.name} decode step, {pred.max_seqs} slots", compiled,
           time.perf_counter() - t0)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--set", action="append", default=[],
                    metavar="key=value")
    args = ap.parse_args(argv)

    from jax.experimental import topologies

    from benchmarks.harness import manifest

    cell = manifest.Cell(manifest.load(pending=True), args.workload)
    for item in args.set:
        key, value = item.split("=", 1)
        group = cell.traffic
        *path, leaf = key.split(".")
        for part in path:
            group = group[part]
        group[leaf] = json.loads(value)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    if cell.traffic["loop"] == "train_steps":
        check_training(cell, topo)
    else:
        check_serving(cell, topo)
    return 0


if __name__ == "__main__":
    sys.exit(main())
