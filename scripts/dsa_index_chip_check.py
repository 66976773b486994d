"""``ops/dsa.py``'s index backward on the chip: the one kernel
`dsa_index_bwd` against the parent's pair (`dsa_index_bwd_dq`, XLA's
transpose of the cotangent, `dsa_index_bwd_dk`), device time of each from
a profiler trace and d``q``, d``k``, d``w`` compared, on the operands of

- **the keye-vl cell's first layer at 16384** (16 index heads of 64) and
- **the dots3 cell's first full layer at 8192** (64 index heads of 128),

each made as ``jobs/finetune_loop.py`` makes them: the cell's
configuration, weights and tokens from ``--seed``, the layer run on the
embedded tokens, the indexer's ``q, k, w`` taken where the layer hands
them to `dsa.index_scores` and the cotangent the KL's own gradient
(`dsa._kl_and_grad`) on the scores, probabilities and mask the layer
hands `dsa.indexer_loss`, over the layer's rows.

    chiprun -- python scripts/dsa_index_chip_check.py [--seed N]
        [--parent DIR] [--cells keye-vl,dots3] [--tiles 256x1024,...]
        [--rehearse]

``--parent`` is a checkout of a commit that still has the pair (``git
archive 662b051 | tar -x -C DIR``): its ``dlrover_tpu/ops/dsa.py`` is
loaded beside this tree's. Without it the fused kernel is compared with
the XLA form's float32 autodiff alone, which both are held to anyway
(the largest and the 99th-percentile row-wise relative difference; a
bf16 result sits at rounding, 2**-8 a term). ``--tiles`` times the
fused kernel once more a ``BQxBK`` given (``ops/dsa.py
_MAX_TILE["dq"]``). ``--rehearse`` runs it here
at the tiny configurations in interpret mode. Prints one JSON object
and writes it to ``chiprun_out/pr57/dsa_index_chip_check.json``.
"""

import argparse
import functools
import importlib.util
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax
import jax.numpy as jnp

from benchmarks.families import dots3 as dots3_family
from benchmarks.families import keye_vl as keye_family
from dlrover_tpu.models import dots3, keye_vl
from dlrover_tpu.ops import dsa, rms_norm
from dlrover_tpu.parallel import MeshConfig, build_mesh
from scripts.dsa_select_chip_check import device_ms, host_ms, largest

CELLS = {
    "keye-vl": ("keye-vl-2.0-30b-a3b-ep8-1chip", "tiny-cpu-keye-vl", 16384),
    "dots3": ("dots3-note-prev-ep32-1chip", "tiny-cpu-dots3", 8192),
}
GRADS = ("dq", "dk", "dw")


def _first_indexed_layer(cell: str, config: dict, fam, mesh, seq: int):
    """``(its parameters from the whole tree, (layer params, y,
    interpret) -> its attention sublayer)`` of the cell's first layer
    with an indexer."""
    cfg = fam.cfg
    if cell == "keye-vl":
        positions = keye_family.positions_for(config, 1, seq)

        def attend(lp, y, interpret):
            return keye_vl.attention(
                cfg, mesh, keye_vl.rotary_tables(cfg, positions), lp, y,
                interpret)

        return functools.partial(keye_vl.layer_params, cfg, layer=0), attend
    positions = jnp.arange(seq, dtype=jnp.int32)[None]

    def attend(lp, y, interpret):
        return dots3.attention(
            cfg, mesh, dots3.FULL, positions, lp, y, interpret)

    return functools.partial(
        dots3.layer_params, cfg,
        layer=cfg.layer_kinds.index(dots3.FULL)), attend


def cell_operands(cell: str, config: dict, seed: int, seq: int,
                  interpret: bool):
    """The indexer's ``(q, k, w)`` and the cotangent of its scores in the
    cell's first layer with an indexer, on the weights and the reference
    batch the job makes from ``seed``."""
    mesh = build_mesh(MeshConfig(dp=-1).resolve(1), devices=jax.devices()[:1])
    family = keye_family if cell == "keye-vl" else dots3_family
    fam = family.build(config, mesh)
    k_params, k_ref, _ = jax.random.split(jax.random.key(seed), 3)
    params = fam.init_params(k_params)
    tokens = jax.random.randint(
        k_ref, (1, seq), 0, fam.cfg.vocab_size, dtype=jnp.int32)
    layer_of, attend = _first_indexed_layer(cell, config, fam, mesh, seq)

    @jax.jit
    def operands(lp, x):
        seen = {}
        scores_of, loss_of = dsa.index_scores, dsa.indexer_loss

        def index_scores(q, k, w, **kw):
            seen["operands"] = (q, k, w.astype(jnp.float32))
            return scores_of(q, k, w, **kw)

        def indexer_loss(scores, probs, mask):
            seen["g"] = dsa._kl_and_grad(scores, probs, mask)[1] / seq
            return loss_of(scores, probs, mask)

        dsa.index_scores, dsa.indexer_loss = index_scores, indexer_loss
        try:
            attend(lp, rms_norm(x.astype(fam.cfg.dtype), lp["attn_norm"],
                                fam.cfg.norm_eps), interpret)
        finally:
            dsa.index_scores, dsa.indexer_loss = scores_of, loss_of
        return seen["operands"] + (seen["g"],)

    return jax.block_until_ready(
        operands(layer_of(params), params["embed"][tokens]))


def load_parent(directory: str):
    """``dlrover_tpu/ops/dsa.py`` of the checkout at ``directory``, beside
    this tree's (its imports resolve here: the pair needs `attention`'s
    tiling helpers alone, which this tree still has)."""
    spec = importlib.util.spec_from_file_location(
        "parent_dsa", os.path.join(directory, "dlrover_tpu", "ops", "dsa.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _row_rel(got, want):
    """Row-wise relative difference over the last axis, float32."""
    got, want = (a.astype(jnp.float32) for a in (got, want))
    norm = jnp.linalg.norm(want, axis=-1)
    return jnp.linalg.norm(got - want, axis=-1) / jnp.where(
        norm > 0, norm, 1.0)


def _readings(got, want) -> dict:
    out = {}
    for name, a, b in zip(GRADS, got, want):
        rel = _row_rel(a, b)
        out[name + "_rel_max"] = float(jnp.max(rel))
        out[name + "_rel_p99"] = float(jnp.percentile(rel, 99))
    return out


@jax.jit
def oracle(q, k, w, g):
    """The XLA form's autodiff on float32 operands."""
    q, k = q.astype(jnp.float32), k.astype(jnp.float32)
    return jax.grad(lambda q, k, w: jnp.sum(
        dsa._index_scores_xla(q, k, w) * g), argnums=(0, 1, 2))(q, k, w)


def _fused(interpret: bool):
    # a jit of its own a call: `_MAX_TILE` is read while it is traced
    return jax.jit(lambda *a: dsa._index_bwd_pallas(*a, interpret))


def compare(name: str, operands, parent, interpret: bool, tiles=()) -> dict:
    """The fused backward against the parent's pair and the float32
    oracle on ``operands``, and what each costs on the device."""
    forms = {"fused": _fused(interpret)}
    if parent is not None:
        forms["parent"] = jax.jit(
            lambda *a: parent._index_bwd_pallas(*a, interpret))
    q, k, w, g = operands
    out = {"q": list(q.shape), "dtype": str(q.dtype),
           "tiles": list(dsa._tiles("dq", q.shape[1])),
           "g_nonzero_share": float(jnp.mean(g != 0))}
    want = oracle(*operands)
    got = {}
    for form, fn in forms.items():
        got[form] = fn(*operands)
        out[form + "_against_float32"] = _readings(got[form], want)
        out[form + "_host_ms"] = host_ms(fn, *operands)
        by = device_ms(fn, *operands)
        out[form + "_device_ms"] = sum(by.values())
        out[form + "_device_largest"] = largest(by, 6)
    if parent is not None:
        out["fused_against_parent"] = _readings(got["fused"], got["parent"])
        out["elements_that_differ"] = {
            name: int(jnp.sum(a != b))
            for name, a, b in zip(GRADS, got["fused"], got["parent"])}
    for probe in tiles:
        held = dsa._MAX_TILE
        dsa._MAX_TILE = dict(held, dq=tuple(int(n) for n in probe.split("x")))
        try:
            fn = _fused(interpret)
            out[f"tiles_{probe}"] = {
                "against_float32": _readings(fn(*operands), want),
                "device_ms": {op: ms for op, ms in device_ms(
                    fn, *operands).items() if op.startswith("dsa_index")}}
        except Exception as e:  # what the compiler refuses, by its words
            out[f"tiles_{probe}"] = {"refused": str(e)[-300:]}
        finally:
            dsa._MAX_TILE = held
    print(f"[dsa_index] {name}: {json.dumps(out)}", flush=True)
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=2147483659)
    ap.add_argument("--parent", default="")
    ap.add_argument("--cells", default="keye-vl,dots3")
    ap.add_argument("--tiles", default="")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    if not args.rehearse and jax.default_backend() != "tpu":
        raise SystemExit("no chip here: --rehearse runs the tiny size")
    parent = load_parent(args.parent) if args.parent else None

    out = {"device": jax.devices()[0].device_kind, "seed": args.seed,
           "parent": args.parent or None}
    for cell in args.cells.split(","):
        real, tiny, seq = CELLS[cell]
        if args.rehearse:
            seq = 256
        with open(os.path.join(ROOT, "benchmarks", "configs", (
                tiny if args.rehearse else real) + ".json")) as f:
            config = json.load(f)
        operands = cell_operands(cell, config, args.seed, seq, args.rehearse)
        out[cell] = compare(cell, operands, parent, args.rehearse,
                            [t for t in args.tiles.split(",") if t])
        del operands

    print(json.dumps(out), flush=True)
    os.makedirs(os.path.join(ROOT, "chiprun_out", "pr57"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "pr57",
                           "dsa_index_chip_check.json"), "w") as f:
        json.dump(out, f, indent=1)
    # the bf16 results' rounding, 2**-8 a term, with room
    worst = max(v for cell in CELLS if cell in out
                for v in out[cell]["fused_against_float32"].values())
    return 0 if worst < 0.05 else 1


if __name__ == "__main__":
    sys.exit(main())
