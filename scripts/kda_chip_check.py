"""``ops/kda.py`` on the chip at the kimi-linear cell's shape, (1, 8192,
32, 128) bfloat16 with ``g`` over the init range: the kernels' time,
forward and forward + backward, against the XLA form's, and their
output and five gradients against the XLA form's and, at 1024 tokens,
the float32 recurrence's; and the two elementwise passes around the
kernels (stages ``inputs``, ``output``), each way, against the XLA ops
they replace: time, output and every gradient.

    chiprun -- python scripts/kda_chip_check.py [--seed N] [--stages a,b]

Prints one JSON object and writes it to ``chiprun_out/kda_chip_check.json``.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from benchmarks.families.kimi_linear import ref_delta_rule
from dlrover_tpu.ops import kda

SHAPE = (1, 8192, 32, 128)


def inputs(seed, shape=SHAPE):
    ks = jax.random.split(jax.random.key(seed), 6)
    d = shape[-1]
    q = jax.random.normal(ks[0], shape)
    k = jax.random.normal(ks[1], shape)
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * d ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], shape)
    g = -1.6 * jax.random.uniform(ks[3], shape, minval=0.0, maxval=1.0)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], shape[:3]))
    w = jax.random.normal(ks[5], shape)
    bf = jnp.bfloat16
    return (q.astype(bf), k.astype(bf), v.astype(bf), g, beta), w.astype(bf)


def timed(fn, *args, reps=10):
    out = jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps * 1e3, out


def rel(got, want):
    got, want = got.astype(jnp.float32), want.astype(jnp.float32)
    return float(jnp.max(jnp.abs(got - want)) / (jnp.max(jnp.abs(want)) + 1e-30))


def passes(seed, stages):
    """The passes against the XLA forms. Every array crosses the jit's
    boundary as ``(b, s, h d)``: a 4-d parameter or result has another
    tiled layout on the chip and would cost a copy neither form makes
    inside the layer. ``*_bwd_ms`` of a pass is its backward alone (its
    residuals are its inputs); of the XLA form, the backward with what
    of the forward autodiff needs again."""
    b, s, h, d = SHAPE
    bf = jnp.bfloat16
    ks = jax.random.split(jax.random.key(seed), 13)

    def flat(key, scale=1.0):
        return (scale * jax.random.normal(key, (b, s, h * d))).astype(bf)

    xs = tuple(flat(k) for k in ks[:3])
    taps = tuple(jax.random.uniform(
        k, (h * d, 4), minval=-0.5, maxval=0.5).astype(bf) for k in ks[3:6])
    cts = tuple(flat(k) for k in ks[6:9])
    o, gate, ct = flat(ks[9]), flat(ks[10], 2.0), flat(ks[11])
    weight = (1.0 + 0.3 * jax.random.normal(ks[12], (d,))).astype(bf)
    scales = (d ** -0.5, 1.0, None)

    def wide(a):
        return a.reshape(b, s, h, d)

    forms = {
        "inputs": (
            lambda xs, taps: tuple(a.reshape(b, s, -1) for a in (
                kda.conv_silu_norm(xs, taps, heads=h, scales=scales))),
            lambda xs, taps: tuple(a.reshape(b, s, -1) for a in (
                kda._conv_silu_norm_xla(xs, taps, h, scales))),
            (xs, taps), cts),
        "output": (
            lambda o, g, w: kda.norm_gate(wide(o), wide(g), w, 1e-5),
            lambda o, g, w: kda._norm_gate_xla(wide(o), wide(g), w, 1e-5),
            (o, gate, weight), ct),
    }

    def backward(fn):
        return jax.jit(lambda ct, *args: jax.vjp(fn, *args)[1](ct))

    res = {}
    for name in stages:
        fused, xla, args, ct = forms[name]
        r = res[name] = {}
        r["xla_fwd_ms"], want = timed(jax.jit(xla), *args)
        r["xla_bwd_ms"], want_grads = timed(backward(xla), ct, *args)
        r["pass_fwd_ms"], got = timed(jax.jit(fused), *args)
        r["pass_bwd_ms"], grads = timed(backward(fused), ct, *args)
        r["out_vs_xla"] = max(
            rel(x, y) for x, y in zip(*map(jax.tree.leaves, (got, want))))
        r["grads_vs_xla"] = [rel(x, y) for x, y in zip(
            *map(jax.tree.leaves, (grads, want_grads)))]
    return res


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--stages",
                    default="xla,fwd,grad,recurrence,inputs,output")
    a = ap.parse_args()
    stages = a.stages.split(",")
    args, w = inputs(a.seed)
    res = {"device": jax.devices()[0].device_kind, "seed": a.seed}

    def loss(form):
        def f(*xs):
            return jnp.sum(form(*xs).astype(jnp.float32) * w)
        return f

    xla = lambda *xs: kda._chunk_kda_xla(*xs, chunk=64)
    ker = lambda *xs: kda.chunk_kda(*xs, chunk=64)
    if "xla" in stages:
        res["xla_fwd_ms"], o_xla = timed(jax.jit(xla), *args)
        res["xla_fwd_bwd_ms"], g_xla = timed(
            jax.jit(jax.grad(loss(xla), argnums=range(5))), *args)
    if "fwd" in stages:
        res["kernel_fwd_ms"], o_ker = timed(jax.jit(ker), *args)
        if "xla" in stages:
            res["out_vs_xla"] = rel(o_ker, o_xla)
    if "grad" in stages:
        res["kernel_fwd_bwd_ms"], g_ker = timed(
            jax.jit(jax.grad(loss(ker), argnums=range(5))), *args)
        if "xla" in stages:
            res["grads_vs_xla"] = dict(zip(
                ("dq", "dk", "dv", "dg", "dbeta"),
                (rel(x, y) for x, y in zip(g_ker, g_xla))))
    if "recurrence" in stages:
        # the float32 recurrence, a token a step, at a length it can run
        short = tuple(x[:, :1024] for x in args)
        ws = w[:, :1024]
        f32 = tuple(x.astype(jnp.float32) for x in short)

        def rloss(form):
            return lambda *xs: jnp.sum(form(*xs).astype(jnp.float32) * ws)
        want = jax.jit(ref_delta_rule)(*f32)
        res["out_vs_recurrence_1024"] = rel(jax.jit(ker)(*short), want)
        res["xla_out_vs_recurrence_1024"] = rel(jax.jit(xla)(*short), want)
        if "grad" in stages:
            gw = jax.jit(jax.grad(rloss(ref_delta_rule), argnums=range(5)))(*f32)
            gk = jax.jit(jax.grad(rloss(ker), argnums=range(5)))(*short)
            gx = jax.jit(jax.grad(rloss(xla), argnums=range(5)))(*short)
            names = ("dq", "dk", "dv", "dg", "dbeta")
            res["grads_vs_recurrence_1024"] = dict(zip(
                names, (rel(x, y) for x, y in zip(gk, gw))))
            res["xla_grads_vs_recurrence_1024"] = dict(zip(
                names, (rel(x, y) for x, y in zip(gx, gw))))
    res.update(passes(
        a.seed, [n for n in ("inputs", "output") if n in stages]))
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/kda_chip_check.json", "w") as f:
        json.dump(res, f, indent=1)
    print(json.dumps(res))


if __name__ == "__main__":
    main()
