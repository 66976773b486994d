"""``ops/dsa.py``'s selection on the chip: the kernel `dsa_select` against
the XLA form (`select_threshold`'s passes over ``(s, s)`` in HBM), the
mask byte for byte and the device time of each from a profiler trace, on

- **the keye-vl cell's own scores at 16384**: the cell's configuration,
  weights and tokens made from ``--seed`` as ``jobs/finetune_loop.py``
  makes them, the first layer's indexer (`families/keye_vl.py
  program_fns`), top-2048;
- the same scores rounded to eighths with NaN above the diagonal (ties
  at every threshold: the ``cut`` search runs, which the seeded scores
  never ask for);
- random index scores at 8192 (the dots3 cell's length, 256-row blocks).

    chiprun -- python scripts/dsa_select_chip_check.py [--seed N]
        [--probe 32x512,64x1024,...] [--rehearse]

``--probe`` times the kernel once more a ``SUBxTRIP`` given (the rows a
run of passes carries, the columns a trip covers: ``ops/dsa.py
_SELECT_SUB``, ``_SELECT_TRIP``). ``--rehearse`` runs it here at a tiny
size in interpret mode. Prints one JSON object and writes it to
``chiprun_out/dsa_select_chip_check.json``.
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

from benchmarks.families import keye_vl as family
from benchmarks.harness import trace_reduce
from dlrover_tpu.ops import dsa
from dlrover_tpu.parallel import MeshConfig, build_mesh

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def device_ms(fn, *args, reps=3):
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


def largest(by: dict, n: int = 4) -> dict:
    return dict(sorted(by.items(), key=lambda kv: -kv[1])[:n])


def host_ms(fn, *args, reps=3):
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps * 1e3


def cell_scores(config: dict, seed: int, seq: int):
    """The first layer's index scores ``(1, seq, seq)`` and the mask the
    layer made of them, on the weights and the reference batch the job
    makes from ``seed``."""
    mesh = build_mesh(MeshConfig(dp=-1).resolve(1), devices=jax.devices()[:1])
    fam = family.build(config, mesh)
    k_params, k_ref, _ = jax.random.split(jax.random.key(seed), 3)
    params = fam.init_params(k_params)
    tokens = jax.random.randint(
        k_ref, (1, seq), 0, fam.cfg.vocab_size, dtype=jnp.int32)
    positions = family.positions_for(config, 1, seq)
    layer = family.program_fns(fam.cfg, mesh, positions)[1]
    out = layer(next(family.layers_of(params)), params["embed"][tokens])
    return out["scores"], out["mask"], fam.cfg.index_topk


def compare(name: str, scores, topk: int, interpret: bool, probes=()):
    """The kernel's mask against the XLA form's on ``scores``, and what
    each costs on the device."""
    kernel = jax.jit(lambda x: dsa.selection_mask(
        x, topk, interpret=interpret))
    xla = jax.jit(lambda x: dsa._xla_selection_mask(x, topk))
    got, want = kernel(scores), xla(scores)
    s = scores.shape[-1]
    out = {
        "shape": list(scores.shape), "topk": topk,
        "rows_a_block": dsa._select_rows(s),
        "bytes_that_differ": int(jnp.sum(got != want)),
        "selected": int(jnp.sum(want.astype(jnp.int32))),
        "kernel_host_ms": host_ms(kernel, scores),
        "xla_host_ms": host_ms(xla, scores),
    }
    del got
    by = device_ms(kernel, scores)
    out["kernel_device_ms"] = sum(by.values())
    out["kernel_device_largest"] = largest(by)
    by = device_ms(xla, scores)
    out["xla_device_ms"] = sum(by.values())
    out["xla_device_largest"] = largest(by)
    for probe in probes:
        sub, trip = (int(n) for n in probe.split("x"))
        held = dsa._SELECT_SUB, dsa._SELECT_TRIP
        dsa._SELECT_SUB, dsa._SELECT_TRIP = sub, trip
        try:
            fn = jax.jit(lambda x: dsa.selection_mask(
                x, topk, interpret=interpret))
            differ = int(jnp.sum(fn(scores) != want))
            out[f"probe_{probe}"] = {
                "bytes_that_differ": differ,
                "device_ms": sum(
                    ms for op, ms in device_ms(fn, scores).items()
                    if op.startswith("dsa_select")),
                "host_ms": host_ms(fn, scores)}
        finally:
            dsa._SELECT_SUB, dsa._SELECT_TRIP = held
    print(f"[dsa_select] {name}: {json.dumps(out)}", flush=True)
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=2147483659)
    ap.add_argument("--probe", default="")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    probes = [p for p in args.probe.split(",") if p]

    name = ("tiny-cpu-keye-vl" if args.rehearse
            else "keye-vl-2.0-30b-a3b-ep8-1chip")
    with open(os.path.join(ROOT, "benchmarks", "configs", name + ".json")) as f:
        config = json.load(f)
    seq, short = (256, 128) if args.rehearse else (16384, 8192)
    if not args.rehearse and jax.default_backend() != "tpu":
        raise SystemExit("no chip here: --rehearse runs the tiny size")

    out = {"device": jax.devices()[0].device_kind, "seed": args.seed}
    scores, layer_mask, topk = cell_scores(config, args.seed, seq)
    want = jax.jit(lambda x: dsa._xla_selection_mask(x, topk))(scores)
    out["cell_layer_mask_bytes_that_differ"] = int(
        jnp.sum(layer_mask != (want != 0)))
    del layer_mask, want
    out["cell_scores"] = compare(
        "cell scores", scores, topk, args.rehearse, probes)
    tied = jnp.where(jnp.tril(jnp.ones((seq, seq), bool)),
                     jnp.round(scores * 8) / 8, jnp.nan)
    del scores
    out["tied_scores_nan_above"] = compare(
        "tied scores, NaN above the diagonal", tied, topk, args.rehearse)
    del tied
    kq, kk, kw = jax.random.split(jax.random.key(args.seed), 3)
    heads, width = (2, 16) if args.rehearse else (16, 64)
    scores = jax.jit(dsa.index_scores, static_argnames="interpret")(
        jax.random.normal(kq, (1, short, heads, width), jnp.bfloat16),
        jax.random.normal(kk, (1, short, width), jnp.bfloat16),
        jax.random.normal(kw, (1, short, heads), jnp.float32),
        interpret=args.rehearse)
    out["random_scores_short"] = compare(
        "random scores", scores, min(topk, short // 4), args.rehearse)

    print(json.dumps(out), flush=True)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(
            ROOT, "chiprun_out", "dsa_select_chip_check.json"), "w") as f:
        json.dump(out, f, indent=1)
    differ = [v["bytes_that_differ"] for v in out.values()
              if isinstance(v, dict)]
    return 1 if any(differ) or out["cell_layer_mask_bytes_that_differ"] else 0


if __name__ == "__main__":
    sys.exit(main())
