"""``ops/moe_rows.py`` on the chip at the expert cells' shapes: the four
row movements of ``models/moe.py`` (dispatch and combine, forward and
backward) bound by the live count against XLA's gathers over every row:
time, and every output and gradient element for element (dispatch's
forward below the count and to the end of its block).

    chiprun -- python scripts/moe_rows_chip_check.py [--seed N]
        [--cells a,b] [--window 8,16] [--tokens 64,128]

A cell's routing is uniform over its experts, so its live rows are the
held share of ``t x k``; ``<cell>/all-live`` runs the same kernels with
every expert held (no tail: what the step itself leaves to XLA).
Prints one JSON object and writes it to
``chiprun_out/moe_rows_chip_check.json``.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from dlrover_tpu.models import moe
from dlrover_tpu.ops import moe_rows

#: cell: tokens, choices a token, experts, experts held, width
CELLS = {
    "smallthinker": (16384, 6, 64, 16, 2560),
    "xing4": (8192, 4, 64, 8, 3584),
    "kimi": (8192, 8, 256, 32, 2304),
    "qwen3next": (16384, 10, 512, 32, 2048),
    "dots3": (8192, 8, 256, 8, 5120),
    "granite": (16384, 10, 72, 9, 4096),
    "olmoe": (8192, 8, 64, 64, 2048),
}


def timed(fn, *args, reps=10):
    out = jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps * 1e3, out


@jax.jit
def _differing(got, want, keep):
    # one fused pass: a (163840, 4096) pair has no room for float32 copies
    bad = ~((got == want) & ~jnp.isnan(got))
    if keep is not None:
        bad = bad & keep.reshape(keep.shape + (1,) * (bad.ndim - keep.ndim))
    gap = jnp.abs(got.astype(jnp.float32) - want.astype(jnp.float32))
    return jnp.sum(bad), jnp.max(jnp.where(bad, gap, 0.0))


def differing(got, want, keep=None):
    """Elements that differ (NaN counts), among the rows ``keep``."""
    count, worst = _differing(got, want, keep)
    return {"differ": int(count), "max_abs": float(worst)}


def check(seed, t, k, e, held, d, interpret=False, profile=False):
    n = t * k
    ks = jax.random.split(jax.random.key(seed), 6)
    bf = jnp.bfloat16
    _, top_e = jax.lax.top_k(jax.random.uniform(ks[0], (t, e)), k)
    order, inverse, sizes = jax.jit(
        lambda te: moe.sort_pairs(te, held, 0))(top_e.astype(jnp.int32))
    live = jnp.sum(sizes)
    below = jnp.arange(n) < live
    yt = jax.random.normal(ks[1], (t, d)).astype(bf)
    weights = jax.random.uniform(ks[2], (t, k), jnp.float32)
    # as the grouped products leave them: zeros past the live count (in
    # one fused pass: float32 normals of granite's shape are 2.5 GiB)
    sorted_rows = jax.jit(lambda key: jnp.where(
        below[:, None], jax.random.normal(key, (n, d)), 0).astype(bf))
    rows = sorted_rows(ks[3])
    g_tokens = jax.random.normal(ks[4], (t, d)).astype(bf)
    g_rows = sorted_rows(ks[5])

    def forms(count):
        dispatch = lambda y: moe.dispatch_rows(
            y, order, inverse, k, count, interpret=interpret)
        combine = lambda r, w: moe.combine_rows(
            r, w, order, inverse, count, interpret=interpret)
        return {
            "dispatch_fwd": (jax.jit(dispatch), (yt,)),
            "dispatch_bwd": (jax.jit(
                lambda y, ct: jax.vjp(dispatch, y)[1](ct)[0]), (yt, g_rows)),
            "combine_fwd": (jax.jit(combine), (rows, weights)),
            "combine_bwd": (jax.jit(
                lambda r, w, ct: jax.vjp(combine, r, w)[1](ct)),
                (rows, weights, g_tokens)),
        }

    res = {"live": int(live), "rows": n,
           "row_blocks": moe_rows.row_blocks(
               t, k, d, bf, interpret=interpret)}
    res["live_pairs_ms"], _ = timed(jax.jit(
        lambda inv, c: moe_rows._live_pairs(inv, c, res["row_blocks"][1] * k)
    ), inverse, live)
    xla, kernels = forms(None), forms(live)

    def choice_major(r, w):
        # XLA's ops with the choices on the major axis: no kernel, no skip
        picked = r[inverse.reshape(t, k).T.reshape(-1)].reshape(k, t, d)
        return jnp.sum(picked.astype(jnp.float32) * w.T[:, :, None],
                       axis=0).astype(r.dtype)

    res["combine_fwd_choice_major_ms"], major = timed(
        jax.jit(choice_major), rows, weights)
    res["combine_fwd_choice_major"] = differing(
        major, xla["combine_fwd"][0](rows, weights))
    live_pair = (inverse < live).reshape(t, k)
    for name in xla:
        r = res[name] = {}
        r["xla_ms"], want = timed(xla[name][0], *xla[name][1])
        r["kernel_ms"], got = timed(kernels[name][0], *kernels[name][1])
        if name == "dispatch_fwd":
            # the rows gathered past the count, to its block's end, are real
            block = res["row_blocks"][0]
            r.update(differing(got, want, jnp.arange(n) < jnp.minimum(
                n, (live // block + 1) * block)))
        elif name == "combine_bwd":
            r["d_rows"] = differing(got[0], want[0], below)
            r["d_weights"] = differing(got[1], want[1], live_pair)
            r["d_weights_of_the_tail_are_zero"] = not bool(
                jnp.any(jnp.where(live_pair, 0.0, got[1])))
            rel = jnp.abs(got[1] - want[1]) / (jnp.abs(want[1]) + 1e-6)
            r["d_weights"]["max_rel"] = float(
                jnp.max(jnp.where(live_pair, rel, 0.0)))
        else:
            r.update(differing(got, want))
        # the bytes a movement has to move: its live rows in and out
        moved = 2 * int(live) * d * 2
        r["kernel_gb_s"] = moved / r["kernel_ms"] / 1e6
        r["xla_gb_s"] = 2 * n * d * 2 / r["xla_ms"] / 1e6
    if profile:
        res["device_ops_ms"] = device_ops(
            [("sort_pairs", jax.jit(lambda te: moe.sort_pairs(te, held, 0)),
              (top_e.astype(jnp.int32),))]
            + [("dispatch_fwd_every_row",) + xla["dispatch_fwd"]]
            + [(name,) + kernels[name] for name in kernels])
    return res


def device_ops(forms, reps=3):
    """``{form: [[device operation, ms a call], ...]}`` from a profiler
    trace of ``reps`` calls of each form, the longest first."""
    import tempfile

    from benchmarks.harness import trace_reduce

    out = {}
    for name, fn, args in forms:
        jax.block_until_ready(fn(*args))
        with tempfile.TemporaryDirectory() as d:
            with jax.profiler.trace(d):
                for _ in range(reps):
                    jax.block_until_ready(fn(*args))
            trace = trace_reduce.load(trace_reduce.find_xplane(d), [])
        out[name] = [[op, round(sec * 1e3 / reps, 4)]
                     for op, sec in trace_reduce.top_ops(trace, 12)]
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--cells", default=",".join(CELLS))
    ap.add_argument("--window", default=str(moe_rows._WINDOW),
                    help="row fetches in flight to sweep: powers of two")
    ap.add_argument("--tokens", default=str(moe_rows._MAX_TOKEN_BLOCK))
    ap.add_argument("--all-live", type=int, default=1)
    ap.add_argument("--profile", action="store_true",
                    help="also a device trace of each kernel form, by op")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny shapes, the kernels in interpret mode")
    a = ap.parse_args()
    res = {"device": jax.devices()[0].device_kind, "seed": a.seed}
    first = int(a.window.split(",")[0])
    for window in map(int, a.window.split(",")):
        for tokens in map(int, a.tokens.split(",")):
            moe_rows._WINDOW, moe_rows._MAX_TOKEN_BLOCK = window, tokens
            for cell in a.cells.split(","):
                t, k, e, held, d = CELLS[cell]
                if a.rehearse:
                    t, d = t // 32, 256
                key = f"{cell}/w{window}/t{tokens}"
                res[key] = check(a.seed, t, k, e, held, d, a.rehearse,
                                 a.profile)
                print(key, json.dumps(res[key]), flush=True)
                if a.all_live and held < e and window == first:
                    res[key + "/all-live"] = check(
                        a.seed, t, k, e, e, d, a.rehearse)
                    print(key + "/all-live",
                          json.dumps(res[key + "/all-live"]), flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/moe_rows_chip_check.json", "w") as f:
        json.dump(res, f, indent=1)
    print(json.dumps(res))


if __name__ == "__main__":
    main()
