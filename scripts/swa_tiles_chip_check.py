"""The flash kernels' tiles under a window, on the chip: each of the three
``_swa`` kernels alone at a list of ``(block_q, block_k)``, device time
from a profiler trace, at the shapes of the three cells that pass a
window:

- **laguna**: ``(1, 16384, 64 on 8, 128)`` bf16, window 512;
- **dots3**: ``(1, 8192, 16 held heads, 256 / 128 wide, group 1)``,
  window 513;
- **smallthinker**: ``(1, 16384, 28 on 4, 128)``, window 4096 (the shape
  whose tiles must not move);

beside what the band walk computes at each tile (`attention.band_work`:
the visited blocks' pairs and the grid's steps, a kv head) and the pair
`choose_tiles` returns there. dq, dk and dv at every tile are compared
with the first tile's (only the order of the float32 sums differs).

    chiprun -- python scripts/swa_tiles_chip_check.py [--shapes laguna,...]
        [--fwd 256x512,...] [--dq ...] [--dkv ...] [--rehearse]

``--rehearse`` runs it here at a tiny size in interpret mode (no device
plane: the times read 0). Prints one JSON object and writes it to
``chiprun_out/pr61/swa_tiles_chip_check.json``. What it found:
``docs/design/kernels.md`` 1b, PR 61.
"""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax
import jax.numpy as jnp

from dlrover_tpu.ops import attention
from scripts.dsa_select_chip_check import device_ms

#: name: (seq, heads, kv heads, q/k width, v width, window)
SHAPES = {
    "laguna": (16384, 64, 8, 128, 128, 512),
    "dots3": (8192, 16, 16, 256, 128, 513),
    "smallthinker": (16384, 28, 4, 128, 128, 4096),
}
TINY = {name: (512, h // hkv * 2, 2, 32, 32, 64 + w % 2)
        for name, (_, h, hkv, _, _, w) in SHAPES.items()}

GROUPED = "256x512,256x256,128x512,128x256,128x128,256x128,512x256"
DEFAULT = {
    "laguna": {"fwd": GROUPED, "dq": GROUPED},
    "smallthinker": {"fwd": "256x512,256x256", "dq": "256x512,256x256",
                     "dkv": "1024x1024,512x512,512x1024,1024x512"},
    "dots3": {
        "fwd": "2048x512,1024x512,512x512,256x512,1024x256,512x256,256x256,"
               "256x128,128x128",
        "dq": "2048x512,1024x512,512x512,256x512,1024x256,512x256,256x256,"
              "256x128,128x128"},
}
DKV = ("1024x1024,512x1024,1024x512,512x512,256x512,512x256,256x256,"
       "128x512,128x256")


def _tiles(text: str):
    return [tuple(int(n) for n in t.split("x")) for t in text.split(",") if t]


def _rel(got, want) -> float:
    got, want = (a.astype(jnp.float32) for a in (got, want))
    return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))


def sweep(name: str, shape, lists, interpret: bool) -> dict:
    s, h, hkv, d, dv, window = shape
    group = h // hkv
    keys = jax.random.split(jax.random.key(61), 4)
    q = jax.random.normal(keys[0], (1, s, h, d), jnp.bfloat16)
    k = jax.random.normal(keys[1], (1, s, hkv, d), jnp.bfloat16)
    v = jax.random.normal(keys[2], (1, s, hkv, dv), jnp.bfloat16)
    do = jax.random.normal(keys[3], (1, s, h, dv), jnp.bfloat16)
    chosen = attention.flash_tiles(s, s, d, group, q.dtype, dv, window=window)
    out = {"shape": list(shape), "chosen": chosen, "fwd": {}, "dq": {},
           "dkv": {}}
    print(f"[swa_tiles] {name}: {shape} chosen {chosen}", flush=True)

    def fwd(tile):
        return jax.jit(lambda q, k, v: attention._flash_fwd_pallas(
            q, k, v, True, *tile, interpret=interpret, window=window))

    o, lse = fwd(chosen["fwd"])(q, k, v)

    def bwd(dq_tile, dkv_tile):
        return jax.jit(lambda *a: attention._flash_bwd_pallas(
            *a, None, True, dq_tile, dkv_tile, interpret=interpret,
            window=window))

    def row(kernel, tile, fn, args, op, want):
        work = attention.band_work(kernel, s, *tile, group, window)
        line = {"band_pct": 100 * work["band"] / work["computed"],
                "steps": work["steps"],
                "vmem_mib": attention._vmem_bytes(
                    kernel, *tile, d, group, 2, dv) / 2**20}
        try:
            got = fn(*args)
            by = device_ms(fn, *args)
            line["ms"] = sum(ms for n, ms in by.items() if n.startswith(op))
            line["against_first"] = [_rel(a, b) for a, b in zip(
                got, want or got)]
        except Exception as e:  # what the compiler refuses, by its words
            got, line["refused"] = want, str(e)[-300:]
        out[kernel]["%dx%d" % tile] = line
        print(f"[swa_tiles] {name} {kernel} {tile}: {json.dumps(line)}",
              flush=True)
        return want or got

    want = None
    for tile in _tiles(lists["fwd"]):
        want = row("fwd", tile, fwd(tile), (q, k, v), "attention_fwd_swa",
                   want)
    args = (q, k, v, o, lse, do)
    want = None
    for tile in _tiles(lists["dq"]):
        want = row("dq", tile, bwd(tile, chosen["dkv"]), args,
                   "attention_bwd_dq_swa", want)
    want = None
    for tile in _tiles(lists["dkv"]):
        want = row("dkv", tile, bwd(chosen["dq"], tile), args,
                   "attention_bwd_dkv_swa", want)
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", default="laguna,dots3,smallthinker")
    for kernel in attention._KERNELS:
        ap.add_argument("--" + kernel, default="")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    if not args.rehearse and jax.default_backend() != "tpu":
        raise SystemExit("no chip here: --rehearse runs the tiny size")
    out = {"device": jax.devices()[0].device_kind}
    for name in args.shapes.split(","):
        lists = {kernel: getattr(args, kernel) or DEFAULT[name].get(
            kernel, DKV) for kernel in attention._KERNELS}
        if args.rehearse:
            lists = dict.fromkeys(lists, "128x128,64x128,128x64")
        out[name] = sweep(name, (TINY if args.rehearse else SHAPES)[name],
                          lists, args.rehearse)
    print(json.dumps(out), flush=True)
    os.makedirs(os.path.join(ROOT, "chiprun_out", "pr61"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "pr61",
                           "swa_tiles_chip_check.json"), "w") as f:
        json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
