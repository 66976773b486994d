"""``ops/hc_mix.py`` on the chip at the xing4 cell's shape, four streams of
(2, 4096, 3584) bfloat16: each of the four passes alone (by the host's
clock, and from a profiler trace of the same jitted call the kernel's
device time, the rate at which it moves the slabs
``hc_mix_bytes_per_step`` counts for it, and the device time of what
XLA runs beside it: a hundred 1-3 us fusions read four times longer by
the host), the ``jnp`` form each replaces, one whole sublayer forward and
forward + backward in both forms (``fn`` the identity: the mixing alone),
and the passes' result and every gradient against the ``jnp`` form's.

    chiprun -- python scripts/hc_chip_check.py [--seed N] [--rehearse]
        [--rows hc_pre_fwd=128,hc_pre_bwd=128 ...]

``--rows`` times the passes once more a value given (a grid step's
tokens; ``ops/hc_mix.py ROWS``). ``--rehearse`` runs it here at a tiny
size in interpret mode. Prints one JSON object and writes it to
``chiprun_out/hc_chip_check.json``.
"""

import argparse
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from benchmarks.harness import peaks, trace_reduce
from dlrover_tpu.models import xing4
from dlrover_tpu.ops import hc_mix

SHAPE = (4, 2, 4096, 3584)
# slabs of (b, s, d) a pass reads and writes, n = 4
SLABS = {"hc_pre_fwd": 5, "hc_post_fwd": 9, "hc_post_bwd": 10,
         "hc_pre_bwd": 13}


def timed(fn, *args, reps=10):
    out = jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps * 1e3, out


def device_ms(fn, *args, reps=5):
    """``{operation: ms a call}`` on the device, from a trace of ``reps``
    calls (the CPU has no device plane: empty there)."""
    jax.block_until_ready(fn(*args))
    with tempfile.TemporaryDirectory() as d:
        with jax.profiler.trace(d):
            for _ in range(reps):
                out = fn(*args)
            jax.block_until_ready(out)
        path = trace_reduce.find_xplane(d)
        devices = trace_reduce.load(path, []).devices if path else {}
    by = {}
    for start, end, name, _ in next(iter(devices.values()), []):
        by[name] = by.get(name, 0.0) + (end - start) / reps / 1e6
    return by


def rel(got, want):
    got, want = got.astype(jnp.float32), want.astype(jnp.float32)
    return float(jnp.max(jnp.abs(got - want)) / (jnp.max(jnp.abs(want)) + 1e-30))


def inputs(seed, shape, cfg):
    n, b, s, d = shape
    bf = jnp.bfloat16
    ks = jax.random.split(jax.random.key(seed), 8)
    X = jax.random.normal(ks[0], shape).astype(bf)
    z = jax.random.normal(ks[1], shape[1:]).astype(bf)
    dXp = jax.random.normal(ks[2], shape).astype(bf)
    dy = jax.random.normal(ks[3], shape[1:]).astype(bf)
    lp = {
        "hc_phi": (0.02 * jax.random.normal(ks[4], (n, d, cfg.hc_width))
                   ).astype(bf),
        "hc_alpha": jnp.full((3,), 0.5, bf),
        "hc_bias": (xing4.hc_bias_init(n)
                    + 0.3 * jax.random.normal(ks[5], (cfg.hc_width,))
                    ).astype(bf),
    }
    return X, z, dXp, dy, lp


def time_passes(hp, X, z, dXp, dy, lp):
    """Each pass's jitted call alone (pass 1 with the coefficients XLA
    forms from its ``raw``, pass 4 with the small backward): ``*_ms`` by
    the host's clock; from a trace, ``*_kernel_ms``, the share of the
    HBM's rate the kernel's slabs make, and ``*_xla_ms`` beside it."""
    n, b, s, d = X.shape
    phi, alpha, bias = lp["hc_phi"], lp["hc_alpha"], lp["hc_bias"]
    slab = b * s * d * X.dtype.itemsize
    out = {}

    def note(name, ms, fn, *args):
        out[name + "_ms"] = ms
        by = device_ms(fn, *args)
        kernel = sum(v for op, v in by.items() if op.startswith(name))
        if kernel:
            rate = peaks.peaks_for(
                jax.devices()[0].device_kind)["hbm_bytes_per_s"]
            out[name + "_kernel_ms"] = kernel
            out[name + "_hbm_share"] = (
                SLABS[name] * slab / rate / (kernel * 1e-3))
            out[name + "_xla_ms"] = sum(by.values()) - kernel

    pre = jax.jit(lambda X, phi, alpha, bias: hc_mix._pre_forward(
        X, phi, alpha, bias, hp))
    ms, (y, raw, H) = timed(pre, X, phi, alpha, bias)
    note("hc_pre_fwd", ms, pre, X, phi, alpha, bias)
    post = jax.jit(lambda H, X, z: hc_mix._post_forward(H, X, z, hp.interpret))
    ms, _ = timed(post, H, X, z)
    note("hc_post_fwd", ms, post, H, X, z)
    post_b = jax.jit(lambda H, X, z, dXp: hc_mix._post_backward(
        hp.interpret, (H, X, z), dXp)[::2])
    ms, (dH, _) = timed(post_b, H, X, z, dXp)
    note("hc_post_bwd", ms, post_b, H, X, z, dXp)
    pre_b = jax.jit(lambda X, phi, alpha, bias, raw, dy, dH, dXp:
                    hc_mix._pre_backward(hp, (X, phi, alpha, bias, raw),
                                         (dy, dH, dXp)))
    ms, _ = timed(pre_b, X, phi, alpha, bias, raw, dy, dH, dXp)
    note("hc_pre_bwd", ms, pre_b, X, phi, alpha, bias, raw, dy, dH, dXp)
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--rows", nargs="*", default=[])
    args = ap.parse_args()

    shape = (4, 2, 80, 256) if args.rehearse else SHAPE
    interpret = args.rehearse
    cfg = xing4.Xing4Config(dim=shape[-1])
    hp = hc_mix.Static(cfg.norm_eps, tuple(cfg.hc_clamp),
                       cfg.hc_sinkhorn_iters, cfg.hc_eps, interpret)
    X, z, dXp, dy, lp = inputs(args.seed, shape, cfg)
    report = {"device": jax.devices()[0].device_kind, "shape": list(shape),
              "rows": dict(hc_mix.ROWS)}

    report["passes"] = time_passes(hp, X, z, dXp, dy, lp)
    for spec in args.rows:
        rows = dict(hc_mix.ROWS)
        for item in spec.split(","):
            name, value = item.split("=")
            rows[name] = int(value)
        saved, hc_mix.ROWS = hc_mix.ROWS, rows
        jax.clear_caches()
        try:
            report["passes rows " + spec] = time_passes(
                hp, X, z, dXp, dy, lp)
        except Exception as e:  # a block the chip's memory refuses
            report["passes rows " + spec] = repr(e)[-400:]
        hc_mix.ROWS = saved
        jax.clear_caches()

    # the jnp form's pieces, forward
    phi, alpha, bias = lp["hc_phi"], lp["hc_alpha"], lp["hc_bias"]

    def jnp_pre(X, phi, alpha, bias):
        h_pre, h_post, h_res = xing4.hc_coefficients(cfg, phi, alpha, bias, X)
        return xing4.hc_pre_mix(h_pre, X), h_post, h_res

    ms, (_, h_post, h_res) = timed(jax.jit(jnp_pre), X, phi, alpha, bias)
    report["jnp_coefficients_pre_mix_ms"] = ms
    ms, _ = timed(jax.jit(xing4.hc_post_mix), h_post, h_res, X, z)
    report["jnp_post_mix_ms"] = ms

    # one sublayer's mixing, fn the identity, both forms
    def sublayer(fused):
        def run(X, lp):
            if fused:
                return xing4.hc_sublayer(cfg, lp, "hc", X, lambda y: y,
                                         interpret=interpret)
            h_pre, h_post, h_res = xing4.hc_coefficients(
                cfg, lp["hc_phi"], lp["hc_alpha"], lp["hc_bias"], X)
            return xing4.hc_post_mix(h_post, h_res, X, xing4.hc_pre_mix(h_pre, X))
        return run

    outs, grads = {}, {}
    for name, fused in (("passes", True), ("jnp", False)):
        run = sublayer(fused)
        ms, outs[name] = timed(jax.jit(run), X, lp)
        report[f"sublayer_{name}_fwd_ms"] = ms
        both = jax.jit(jax.grad(
            lambda X, lp: jnp.sum(run(X, lp).astype(jnp.float32)
                                  * dXp.astype(jnp.float32)),
            argnums=(0, 1)))
        ms, grads[name] = timed(both, X, lp)
        report[f"sublayer_{name}_fwd_bwd_ms"] = ms
        report[f"sublayer_{name}_fwd_bwd_device_ms"] = sum(
            device_ms(both, X, lp).values())
    report["against_jnp"] = {"out": rel(outs["passes"], outs["jnp"])}
    for (path, got), want in zip(
            jax.tree_util.tree_leaves_with_path(grads["passes"]),
            jax.tree.leaves(grads["jnp"])):
        report["against_jnp"]["d" + jax.tree_util.keystr(path)] = rel(got, want)

    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/hc_chip_check.json", "w") as f:
        json.dump(report, f, indent=1)
    print(json.dumps(report, indent=1))


if __name__ == "__main__":
    main()
