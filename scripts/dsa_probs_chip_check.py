"""``ops/dsa.py``'s `dsa_probs` on the chip: the kernel as the tree has it
against a parent checkout's and the XLA form, device time of each from a
profiler trace and every element compared, at

- **the keye-vl cell's call** (``q (1, 16384, 32, 128)`` on 4 key heads) and
- **the dots3 cell's** (``q, k (1, 8192, 32, 192)``),

on random bf16 operands from ``--seed``, a random top-2048 selection and
the ``lse`` the `_sel` flash forward gives under it.

    chiprun -- python scripts/dsa_probs_chip_check.py [--seed N]
        [--parent DIR] [--cells keye-vl,dots3] [--trips 4,16,...]
        [--tiles 256x512,...] [--rehearse]

``--parent`` is a checkout of a commit whose kernel is to be compared
(``git archive 7649bdc | tar -x -C DIR``: the heads in the grid): its
``dlrover_tpu/ops/dsa.py`` is loaded beside this tree's. ``--trips``
times the kernel once more a count of heads a trip
(``_PROBS_HEADS_A_TRIP``), ``--tiles`` a ``BQxBK`` (``_MAX_TILE["probs"]``).
``--rehearse`` runs it here at 1024 positions in interpret mode. Prints
one JSON object and writes it to
``chiprun_out/pr62/dsa_probs_chip_check.json``. The stage table of PR 62
(forms with the ``exp`` stubbed out and the like: ``docs/design/kernels.md``
1f) came from a scratch copy of the kernel, which is not kept.
"""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax
import jax.numpy as jnp

from dlrover_tpu.ops import dsa
from dlrover_tpu.ops.attention import flash_attention
from scripts.dsa_index_chip_check import load_parent
from scripts.dsa_select_chip_check import device_ms

#: (positions, query heads, key heads, head width)
CELLS = {"keye-vl": (16384, 32, 4, 128), "dots3": (8192, 32, 32, 192)}
TOPK = 2048


def operands(s: int, h: int, hkv: int, d: int, seed: int, interpret: bool):
    kq, kk, kv, ks = jax.random.split(jax.random.key(seed), 4)
    q = jax.random.normal(kq, (1, s, h, d), jnp.bfloat16)
    k = jax.random.normal(kk, (1, s, hkv, d), jnp.bfloat16)
    v = jax.random.normal(kv, (1, s, hkv, 128), jnp.bfloat16)
    mask = jax.jit(lambda x: dsa.selection_mask(
        x, min(TOPK, s // 4), interpret=interpret))(
            jax.random.normal(ks, (1, s, s), jnp.float32))
    _, lse = jax.jit(lambda q, k, v, m: flash_attention(
        q, k, v, causal=True, scale=d ** -0.5, select=m, return_lse=True,
        interpret=interpret))(q, k, v, mask)
    return jax.block_until_ready((q, k, lse, mask))


def _kernel(module, scale: float, interpret: bool):
    # a jit of its own a call: the tiles and the trip are read while it
    # is traced
    return jax.jit(lambda *a: module._probs_pallas(*a, scale, interpret))


def _timed(fn, *args) -> float:
    return sum(ms for op, ms in device_ms(fn, *args).items()
               if "dsa_probs" in op)


def compare(name: str, shape, args, parent, interpret: bool) -> dict:
    s, h, hkv, d = shape
    scale = d ** -0.5
    ops = operands(s, h, hkv, d, args.seed, interpret)
    want = jax.jit(lambda *a: dsa._probs_xla(*a, scale))(*ops)
    fn = _kernel(dsa, scale, interpret)
    got = fn(*ops)
    # the causal pairs at two FLOPs a multiply-add, as
    # `benchmarks/harness/keye_vl_flops.py` counts them
    flops = s * (s + 1) // 2 * 2 * d * h
    out = {
        "shape": list(shape), "tiles": list(dsa._probs_tiles(s, h, hkv, d, 2)),
        "heads_a_trip": dsa._probs_trip(h),
        "gflop": flops / 1e9, "device_ms": _timed(fn, *ops),
        "max_abs_against_xla": float(jnp.max(jnp.abs(got - want))),
        "row_sums": [float(jnp.min(got.sum(-1))), float(jnp.max(got.sum(-1)))],
        "nonzero_outside_the_mask": int(jnp.sum((got != 0) & (ops[3] == 0))),
    }
    if parent is not None:
        fn = _kernel(parent, scale, interpret)
        held = fn(*ops)
        out["parent_device_ms"] = _timed(fn, *ops)
        out["elements_that_differ_from_parent"] = int(jnp.sum(got != held))
        del held
    del got, want
    def probed(label: str, attr: str, value):
        held = getattr(dsa, attr)
        setattr(dsa, attr, value)
        try:
            out[label + "_device_ms"] = _timed(
                _kernel(dsa, scale, interpret), *ops)
        except Exception as e:  # what the compiler refuses, by its words
            out[label + "_refused"] = str(e)[-300:]
        finally:
            setattr(dsa, attr, held)

    for t in filter(None, args.trips.split(",")):
        probed(f"trip_{t}", "_PROBS_HEADS_A_TRIP", int(t))
    for t in filter(None, args.tiles.split(",")):
        probed(f"tiles_{t}", "_MAX_TILE", dict(
            dsa._MAX_TILE, probs=tuple(int(n) for n in t.split("x"))))
    print(f"[dsa_probs] {name}: {json.dumps(out)}", flush=True)
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=2147483659)
    ap.add_argument("--parent", default="")
    ap.add_argument("--cells", default="keye-vl,dots3")
    ap.add_argument("--trips", default="")
    ap.add_argument("--tiles", default="")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    if not args.rehearse and jax.default_backend() != "tpu":
        raise SystemExit("no chip here: --rehearse runs the tiny size")
    parent = load_parent(args.parent) if args.parent else None
    out = {"device": jax.devices()[0].device_kind, "seed": args.seed,
           "parent": args.parent or None}
    for cell in args.cells.split(","):
        s, h, hkv, d = CELLS[cell]
        if args.rehearse:
            s, h, hkv = 1024, 8, max(hkv // 4, 1)
        out[cell] = compare(cell, (s, h, hkv, d), args, parent, args.rehearse)
    print(json.dumps(out), flush=True)
    os.makedirs(os.path.join(ROOT, "chiprun_out", "pr62"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "pr62",
                           "dsa_probs_chip_check.json"), "w") as f:
        json.dump(out, f, indent=1)
    ok = all(out[c]["max_abs_against_xla"] < 1e-4
             and out[c]["nonzero_outside_the_mask"] == 0
             for c in CELLS if c in out)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
