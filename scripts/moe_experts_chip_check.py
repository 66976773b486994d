"""The expert products and the pass between them on the chip at the
expert cells' shapes: the walk that zeroes the tail against the walk
bound by the live count (``tail_unread``), ``act(gate) x up`` as XLA's
fusion against ``ops/moe_rows.py``'s pass, and one whole layer, forward
and backward under remat, in both forms: time, and every element below
the count.

    chiprun -- python scripts/moe_experts_chip_check.py [--seed N]
        [--cells a,b] [--static-grid]

A cell's routing is uniform over its experts. ``--static-grid`` also
times the bounded walk with the grid's static bound in place of the
count of visits (what a step past the last visit costs). Prints one
JSON object and writes it to ``chiprun_out/moe_experts_chip_check.json``.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from dlrover_tpu.models import moe
from dlrover_tpu.ops import grouped_matmul as gm
from scripts.moe_rows_chip_check import differing, timed

#: cell: tokens, choices a token, experts, experts held, width, expert
#: width, activation
CELLS = {
    "smallthinker": (16384, 6, 64, 16, 2560, 768, "relu"),
    "dots3": (8192, 8, 256, 8, 5120, 1536, "silu"),
    "kimi": (8192, 8, 256, 32, 2304, 1024, "silu"),
    "xing4": (8192, 4, 64, 8, 3584, 1024, "silu"),
    "olmoe": (8192, 8, 64, 64, 2048, 1024, "silu"),
}


def check(seed, t, k, e, held, d, f, act, interpret=False, static=False):
    n = t * k
    ks = jax.random.split(jax.random.key(seed), 9)
    bf = jnp.bfloat16
    normal = lambda key, shape, scale=1.0: (
        jax.random.normal(key, shape) * scale).astype(bf)
    _, top_e = jax.lax.top_k(jax.random.uniform(ks[0], (t, e)), k)
    top_e = top_e.astype(jnp.int32)
    top_p = jax.random.uniform(ks[1], (t, k), jnp.float32)
    _, _, sizes = jax.jit(lambda te: moe.sort_pairs(te, held, 0))(top_e)
    live = jnp.sum(sizes)
    below = jnp.arange(n) < live
    lp = {"w_gate": normal(ks[2], (held, d, f), d ** -0.5),
          "w_up": normal(ks[3], (held, d, f), d ** -0.5),
          "w_down": normal(ks[4], (held, f, d), f ** -0.5)}
    yt = normal(ks[5], (t, d))
    xs = normal(ks[6], (n, d))
    ct_f = normal(ks[7], (n, f))
    ct_d = normal(ks[8], (n, d))
    res = {"live": int(live), "rows": n,
           "tiles": gm.choose_tiles(n, d, f, bf)}

    gate_up = (lp["w_gate"], lp["w_up"])

    # every array is an argument: a closed-over one is a constant of
    # the executable (the weights are 0.3 GB) and takes a minute to compile
    def products(unread):
        mm = lambda a, ws: gm.grouped_matmuls(
            a, ws, sizes, tail_unread=unread, interpret=interpret)
        return {
            "gate_up_fwd": (jax.jit(mm), (xs, gate_up)),
            "down_fwd": (jax.jit(mm), (ct_f, (lp["w_down"],))),
            "gate_up_bwd": (jax.jit(lambda a, ws, cts: jax.vjp(mm, a, ws)[1](
                cts)), (xs, gate_up, (ct_f, ct_f))),
            "down_bwd": (jax.jit(lambda a, ws, cts: jax.vjp(mm, a, ws)[1](
                cts)), (ct_f, (lp["w_down"],), (ct_d,))),
        }

    def gated(count):
        fn = lambda g, u: moe.gated_rows(g, u, act, count,
                                         interpret=interpret)
        return {
            "gated_fwd": (jax.jit(fn), (ct_f, ct_f)),
            "gated_bwd": (jax.jit(lambda g, u, c: jax.vjp(fn, g, u)[1](c)),
                          (ct_f, ct_f, ct_f)),
        }

    def layer(tail):
        def loss(lp, yt, top_p, top_e):
            fn = jax.checkpoint(
                lambda lp, yt, top_p: moe._experts(
                    lp, yt, top_p, top_e, held, 0, act, tail,
                    interpret=interpret),
                policy=jax.checkpoint_policies.nothing_saveable)
            return jnp.sum(fn(lp, yt, top_p).astype(jnp.float32) ** 2)
        return {"layer": (jax.jit(jax.value_and_grad(loss, (0, 1, 2))),
                          (lp, yt, top_p, top_e))}

    zeroing = {**products(False), **gated(None), **layer(False)}
    bounded = {**products(True), **gated(live), **layer(held < e)}
    for name in zeroing:
        r = res[name] = {}
        r["zeroing_ms"], want = timed(zeroing[name][0], *zeroing[name][1])
        r["bounded_ms"], got = timed(bounded[name][0], *bounded[name][1])
        leaves = zip(jax.tree.leaves(got), jax.tree.leaves(want))
        r["differ"] = [
            differing(g, w, below if g.shape[:1] == (n,) else None)
            for g, w in leaves]
    if static:
        steps = gm._grid_steps
        gm._grid_steps = lambda meta: meta[1].shape[0]
        try:
            for name, (fn, args) in products(True).items():
                res[name]["bounded_static_grid_ms"], _ = timed(fn, *args)
        finally:
            gm._grid_steps = steps
    return res


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--cells", default=",".join(CELLS))
    ap.add_argument("--static-grid", action="store_true")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny shapes, the kernels in interpret mode")
    a = ap.parse_args()
    res = {"device": jax.devices()[0].device_kind, "seed": a.seed}
    for cell in a.cells.split(","):
        t, k, e, held, d, f, act = CELLS[cell]
        if a.rehearse:
            t, d, f = t // 64, 256, 128
        res[cell] = check(a.seed, t, k, e, held, d, f, act, a.rehearse,
                          a.static_grid)
        print(cell, json.dumps(res[cell]), flush=True)
    if not a.rehearse:   # the file holds a chip's numbers and no others
        os.makedirs("chiprun_out", exist_ok=True)
        with open("chiprun_out/moe_experts_chip_check.json", "w") as f:
            json.dump(res, f, indent=1)
    print(json.dumps(res))


if __name__ == "__main__":
    main()
