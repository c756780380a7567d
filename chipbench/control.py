#!/usr/bin/env python3
"""Readings that set the limits of the correctness comparison.

    python chipbench/control.py --workload <cell> --seconds <s> --seeds <n> [<n> ...]
        [--fault unchanged]

For each seed, in one process: set up the cell, run one short window
through the timed path, and compare its outputs with the host reference
(the program's reading).  Then the control: the same outputs with every
compared distance recomputed by the reference at the next precision
below the configuration's float32-at-HIGHEST, i.e. bfloat16x3 (the
``Precision.HIGH`` split: each operand as a bfloat16 pair, three products
kept), and compared again (the control's reading).  The control has to
come out as not correct.

``--fault unchanged`` plants the fault of a build that returns its
state unchanged (``core.build.fused_vamana_pass`` hands back the initial
random graph), so that the recall gap of a graph that was never built can
be read at the cell's size.

Prints one JSON line per seed.  Run it on the chip; the benchmark's own
runs never run it.
"""
from __future__ import annotations

import argparse
import json
import sys

import numpy as np

import run


def _bf16(x):
    """Round float32 to bfloat16's 8 significant bits, kept in float32.
    ``reduce_precision`` is an op XLA must honour; a convert pair to
    bfloat16 and back may be removed as excess precision on the TPU."""
    import jax
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def _split(x):
    hi = _bf16(x)
    return hi, _bf16(x - hi)


def bf16x3_sqdist(a, b):
    """Squared L2 of paired rows by the norm expansion, the cross term a
    bfloat16x3 dot (products of bfloat16 values are exact in float32), the
    norms in float32."""
    import jax.numpy as jnp
    a = jnp.asarray(a, jnp.float32)
    b = jnp.asarray(b, jnp.float32)
    ah, al = _split(a)
    bh, bl = _split(b)
    cross = (ah * bh).sum(-1) + (ah * bl).sum(-1) + (al * bh).sum(-1)
    return (a * a).sum(-1) + (b * b).sum(-1) - 2.0 * cross


def _redo(xa, xb, ids, block: int = 1 << 15):
    """bf16x3 distances between xa rows and xb[ids] (ids INVALID -> inf)."""
    import jax
    f = jax.jit(bf16x3_sqdist)
    flat_a = xa.reshape(-1, xa.shape[-1])
    flat_i = ids.reshape(-1)
    out = np.full(flat_i.shape, np.inf, np.float32)
    for off in range(0, flat_i.size, block):
        i = flat_i[off:off + block]
        ok = i >= 0
        if ok.any():
            d = np.asarray(f(flat_a[off:off + block][ok], xb[i[ok]]))
            seg = out[off:off + block]
            seg[ok] = d
    return out.reshape(ids.shape)


def control_outputs(cell) -> None:
    """Replace every distance the comparison reads with the control's."""
    x = cell.x_host
    if hasattr(cell, "nodes"):                       # estimate
        graphs = []
        for ids, dist, deg in cell.out["graphs"]:
            dist = dist.copy()
            for g in range(ids.shape[0]):
                rows = np.broadcast_to(x[cell.nodes][:, None, :],
                                       ids[g][cell.nodes].shape + x.shape[1:])
                dist[g][cell.nodes] = _redo(rows, x, ids[g][cell.nodes])
            graphs.append((ids, dist, deg))
        pools = []
        for per_cfg in cell.out["pools"]:
            pools.append([(ef, ids, _redo(np.broadcast_to(
                cell.q_host[:, None, :], ids.shape + x.shape[1:]), x, ids))
                for ef, ids, _ in per_cfg])
        cell.out = dict(cell.out, graphs=graphs, pools=pools)
    else:                                            # batched / single
        out = []
        for rows, ids, _ in cell.out:
            q = cell.q_host[np.atleast_1d(rows)]
            qb = np.broadcast_to(q[:, None, :], ids.shape + x.shape[1:])
            out.append((rows, ids, _redo(qb, x, ids)))
        cell.out = out


def plant_unchanged() -> None:
    """The build hands back its initial graph: a state left unchanged."""
    import jax.numpy as jnp
    from repro.core import build

    def unchanged(graph_ids, graph_dist, data, L, M, alpha, ep, *,
                  batch_size, **kw):
        n_batches = -(-data.shape[0] // batch_size)
        return graph_ids, graph_dist, jnp.zeros((n_batches, 4), jnp.int32)
    build.fused_vamana_pass = unchanged


def readings(spec, seed: int, seconds: float, warm: bool) -> dict:
    import jax
    kind = run._module(spec["kind_file"], "kind_" + spec["mix"]["kind"])
    cell = kind.Cell(spec["config"], spec["mix"], seed,
                     jax.profiler.TraceAnnotation)
    if warm:
        cell.setup()
    else:
        cell.setup(warm=False)
    cell.window(seconds)
    cell.release()
    program = cell.compare()
    control_outputs(cell)
    control = cell.compare()
    return {"seed": seed, "program": program, "control": control,
            "program_correct": run.reference.verdict(
                program, spec["limits"])[0],
            "control_correct": run.reference.verdict(
                control, spec["limits"])[0]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=0.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--fault", choices=("unchanged",))
    args = ap.parse_args(argv)
    spec = run.cell_spec(args.workload)
    run.preflight(spec["chips"])
    run.compile_cache()
    if args.fault == "unchanged":
        plant_unchanged()
    warm = spec["mix"]["kind"] != "estimate"
    for seed in args.seeds:
        print(json.dumps(readings(spec, seed, args.seconds, warm)),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
