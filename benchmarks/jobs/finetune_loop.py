"""``train_loop``'s steady cycle (one step a cycle, no save) for a cell
whose configuration states its optimizer and whose family can count the
expert layers' live rows.

``jobs/train_loop.py`` builds ``TrainConfig`` from the batch sizes alone
and keeps its state to itself. Both are what this cell cannot live with:
at the defaults' learning rate 58 steps on uniform tokens collapse a
router (PERF.md section 6, PR 37), so the step drifts through the
window, and nothing but the state says how many rows the held experts
are sent. So, beside what ``train_loop`` does (the same set-up, the same
reference check and tolerances, the same warm-up, window, rate and
percentile, the same traced stretch after the window):

- ``TrainConfig`` takes ``fam.train_config``, the arguments the
  configuration states under ``assumed.train_config``;
- ``fam.live_rows(params, tokens)`` (per layer, the (token, choice)
  pairs that chose a held expert) is read on one batch of the cell's
  traffic that no step trains on, **before the window's first step,
  after its last and after the traced stretch**, never inside a timed
  step. The counters
  ``live_rows`` (a layer's mean at the window's end) and
  ``live_rows_drift_pct`` (the largest change of a layer across the
  window, of its start) are what ``st_moe_live_rows`` and
  ``st_moe_live_rows_drift`` read; the log has every layer;
- the log has the program's gauges of the build (no metric reader logs).

The cell's file gives ``seq``, ``batch``, ``trace_steps`` and
``reference_seq`` as ``train_loop``'s do; ``save_every`` must be 0.
"""

from __future__ import annotations

import math
import time

import numpy as np

from benchmarks.harness import stats
from benchmarks.jobs.train_loop import (
    FIRST_LOSS_TOLERANCE,
    REFERENCE_TOLERANCE,
)

SPAN_NAMES = ("batch", "step")
GAUGES = ("attn.", "layers.", "moe.", "fused_ce.", "step.hbm_")


def run(ctx) -> dict:
    import jax
    import jax.numpy as jnp

    from dlrover_tpu.observability import trace
    from dlrover_tpu.parallel import MeshConfig, build_mesh
    from dlrover_tpu.train.trainer import ElasticTrainer, TrainConfig

    log = ctx.log
    p = ctx.cell["params"]
    seq, batch = int(p["seq"]), int(p["batch"])
    trace_steps = int(p.get("trace_steps", 5))
    ref_seq = int(p.get("reference_seq", 512))
    if int(p.get("save_every", 0)):
        raise ValueError("finetune_loop does not save: use train_loop")

    mc = MeshConfig(dp=-1, **ctx.config.get("mesh", {})).resolve(
        len(ctx.devices))
    mesh = build_mesh(mc, devices=ctx.devices)
    fam = ctx.family.build(ctx.config, mesh)
    dp = mc.data_parallel_size
    if batch % dp:
        raise ValueError(f"batch {batch} does not divide over {dp} shards")
    tc = TrainConfig(global_batch_size=batch, micro_batch_size=batch // dp,
                     **fam.train_config)
    trainer = ElasticTrainer(fam.loss_fn, fam.param_specs, mesh, mc, tc)

    k_params, k_ref, k_data = jax.random.split(jax.random.key(ctx.seed), 3)
    t = time.perf_counter()
    params = fam.init_params(k_params)
    state = trainer.init_state(params)
    del params
    jax.block_until_ready(state)
    state_bytes = sum(l.nbytes for l in jax.tree.leaves(state))
    log(f"state: params={fam.param_count} bytes={state_bytes} "
        f"mesh={dict(mesh.shape)} init_s={time.perf_counter() - t:.2f} "
        f"train_config={fam.train_config}")

    # -- the program against the plain reference, on one seeded batch ----
    t = time.perf_counter()
    ref_tokens = jax.random.randint(
        k_ref, (dp, ref_seq), 0, fam.cfg.vocab_size, dtype=jnp.int32)
    program_loss = float(jax.jit(fam.loss_fn)(state["params"], ref_tokens))
    reference = fam.reference_loss(state["params"], ref_tokens)
    reference_ok = abs(program_loss - reference) <= REFERENCE_TOLERANCE
    log(f"reference: program_loss={program_loss:.5f} "
        f"reference_loss={reference:.5f} "
        f"diff={abs(program_loss - reference):.5f} "
        f"tolerance={REFERENCE_TOLERANCE} ok={reference_ok} "
        f"s={time.perf_counter() - t:.2f}")

    accum, per_accum = trainer.step_batch_shape
    make_batch = jax.jit(
        lambda step: jax.random.randint(
            jax.random.fold_in(k_data, step), (accum, per_accum, seq), 0,
            fam.cfg.vocab_size, dtype=jnp.int32),
        out_shardings=trainer.batch_sharding,
    )

    attempted = failed = 0
    step_no = 0
    losses = []

    def one_step():
        """A step ended by fetching its loss; returns its wall seconds."""
        nonlocal state, step_no, attempted, failed
        attempted += 1
        t0 = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation("batch"):
                tokens = make_batch(np.int32(step_no))
            with jax.profiler.TraceAnnotation("step"):
                state, loss = trainer.step(state, tokens)
                loss = float(loss)
        except Exception:
            failed += 1
            raise
        step_no += 1
        losses.append(loss)
        if not math.isfinite(loss):
            failed += 1
        return time.perf_counter() - t0

    # one batch of the cell's traffic that no step trains on: read on it
    # every time, a change is the parameters' and not the sample's
    probe = make_batch(np.int32(2**31 - 1))[0]

    def live_rows(when: str):
        """The held experts' rows a layer on ``probe``, outside every
        timed step."""
        rows = np.asarray(fam.live_rows(state["params"], probe))
        log(f"live rows {when} (step {step_no}): {rows.tolist()} "
            f"mean {rows.mean():.1f}")
        return rows

    # -- warm-up: the step build, then steady steps ----------------------
    t = time.perf_counter()
    one_step()
    first_loss = losses[0]
    first_step_s = time.perf_counter() - t
    build = dict(getattr(trainer, "_last_build_info", None) or {})
    first_ok = abs(first_loss - fam.expected_first_loss) <= FIRST_LOSS_TOLERANCE
    log(f"step build: cache={build.get('cache')} "
        f"compile_s={build.get('compile_s')} first_step_s={first_step_s:.2f}")
    log(f"first loss {first_loss:.4f} expected "
        f"{fam.expected_first_loss:.4f} +- {FIRST_LOSS_TOLERANCE} "
        f"ok={first_ok}")
    # what the program's gauges say of the build (the operator's; the
    # line carries none): the layout, the window and its tiles, what the
    # router reads, the compiled step's memory
    gauges = trace.gauges()
    log("gauges: " + " ".join(
        [f"{name}={value:.6g}" for name, value in sorted(gauges.items())
         if name.startswith(GAUGES)]
        + [f"layers.pattern={trace.text('layers.pattern')}"]))
    one_step()
    rows_start = live_rows("before the window")

    # -- the measured window ----------------------------------------------
    attempted = failed = 0
    window_t0 = time.perf_counter()
    setup_s = window_t0 - ctx.t_start
    deadline = window_t0 + ctx.seconds
    step_s = []
    while not step_s or time.perf_counter() < deadline:
        sec = one_step()
        if not step_s or time.perf_counter() <= deadline:
            step_s.append(sec)
    window_s = time.perf_counter() - window_t0
    window_attempted, window_failed = attempted, failed
    rows_end = live_rows("after the window")

    # -- a traced stretch, after the window, in a run of its own ----------
    if ctx.trace_dir:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(ctx.trace_dir, profiler_options=options)
        try:
            for _ in range(trace_steps):
                one_step()
        finally:
            jax.profiler.stop_trace()
        live_rows("after the traced stretch")

    all_finite = all(math.isfinite(l) for l in losses)
    log(f"window: {len(step_s)} steps in {sum(step_s):.3f}s of "
        f"{window_s:.3f}s; last loss {losses[-1]:.4f}")
    log(f"step seconds: n={len(step_s)} median={stats.median(step_s):.4f} "
        f"p95={stats.percentile(step_s, 0.95):.4f} max={max(step_s):.4f} "
        f"min={min(step_s):.4f} first half median="
        f"{stats.median(step_s[:len(step_s) // 2] or step_s):.4f} second "
        f"{stats.median(step_s[len(step_s) // 2:]):.4f}")

    tokens_per_s = len(step_s) * batch * seq / sum(step_s)
    return {
        "correct": bool(all_finite and first_ok and reference_ok
                        and failed == 0),
        "attempted": window_attempted,
        "failed": window_failed,
        "end_to_end": {
            "tokens_per_s": (tokens_per_s, "tokens/s"),
            "step_p95_ms": (stats.percentile(step_s, 0.95) * 1e3, "ms"),
            "setup_s": (setup_s, "s"),
        },
        "counters": {
            "build_s": build.get("compile_s") or 0.0,
            "step_ms": stats.median(step_s) * 1e3,
            "tokens_per_s": tokens_per_s,
            "flops_per_token": fam.flops_per_token(seq),
            "chips": len(ctx.devices),
            "live_rows": float(rows_end.mean()),
            "live_rows_drift_pct": float(100.0 * np.max(
                np.abs(rows_end - rows_start) / np.maximum(rows_start, 1))),
        },
        "span_names": SPAN_NAMES,
    }
