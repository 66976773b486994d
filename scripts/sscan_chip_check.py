"""``ops/selective_scan.py`` on the chip, through the chip tool: the two
kernels at the phi4flash cell's shape (16384 tokens, 5120 channels, 16
states, chunks of 256) timed, and at a smaller shape held to the chunked
XLA form, output and all six gradients (about 2 min)::

    chiprun -- python scripts/sscan_chip_check.py [--rehearse]

``--rehearse`` runs it here at a tiny size (interpret mode, no timing
worth reading)."""

import argparse
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import jax
import jax.numpy as jnp

from dlrover_tpu.ops import selective_scan as ss


def operands(seed, b, s, c, n, dtype):
    ks = jax.random.split(jax.random.key(seed), 7)
    x = jax.random.normal(ks[0], (b, s, c)).astype(dtype)
    B = (jax.random.normal(ks[1], (b, s, n)) * 0.3).astype(dtype)
    C = (jax.random.normal(ks[2], (b, s, n)) * 0.3).astype(dtype)
    dt = jnp.exp(jax.random.uniform(ks[3], (b, s, c), minval=-6.9,
                                    maxval=-2.3))
    A = -jnp.broadcast_to(jnp.arange(1, n + 1, dtype=jnp.float32), (c, n))
    D = jnp.ones((c,), jnp.float32)
    ct = jax.random.normal(ks[6], (b, s, c)).astype(dtype)
    return (x, dt, A, B, C, D), ct


def rel(a, b):
    a, b = (jnp.asarray(v, jnp.float32) for v in (a, b))
    return float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-30))


def timed(fn, *args, n=5):
    jax.block_until_ready(fn(*args))
    t = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t) / n * 1e3


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rehearse", action="store_true")
    a = ap.parse_args()
    interpret = a.rehearse
    chunk = 8 if a.rehearse else 256
    small = (1, 32, 128, 16) if a.rehearse else (1, 2048, 1024, 16)
    big = (1, 64, 256, 16) if a.rehearse else (1, 16384, 5120, 16)
    print("device", jax.devices()[0].device_kind, flush=True)

    def kernels(args, ct):
        y, vjp = jax.vjp(lambda *v: ss.selective_scan(
            *v, chunk=chunk, interpret=interpret), *args)
        return y, vjp(ct)

    def oracle(args, ct):
        y, vjp = jax.vjp(lambda *v: ss._chunked_xla(
            *(w.astype(jnp.float32) for w in v), chunk), *args)
        return y, vjp(ct.astype(jnp.float32))

    args, ct = operands(0, *small, jnp.bfloat16)
    y, d = jax.jit(kernels)(args, ct)
    yo, do = jax.jit(oracle)(args, ct)
    print(f"small {small}: y {rel(y, yo):.2e} " + " ".join(
        f"d{name} {rel(g, w):.2e}" for name, g, w in zip(
            ("x", "dt", "A", "B", "C", "D"), d, do)), flush=True)

    args, ct = operands(1, *big, jnp.bfloat16)
    fwd = jax.jit(lambda args: ss.selective_scan(
        *args, chunk=chunk, interpret=interpret))
    print(f"big {big}: fwd {timed(fwd, args):.2f} ms, fwd+bwd "
          f"{timed(jax.jit(kernels), args, ct):.2f} ms", flush=True)


if __name__ == "__main__":
    main()
