"""One measured loop: pre-training steps through ``ElasticTrainer``, with
a flash-checkpoint save to the memory tier every ``save_every`` steps
where the cell asks for one.

Everything runs in this process through the paths PR 22 ran on the chip
(``examples/llama_pretrain.py``): no launcher, agent or master. The
cell's file gives ``seq``, ``batch`` (sequences a step, the global
batch; micro = global, no accumulation), ``save_every`` (0 = never),
``trace_steps`` (how many steps a traced run records where the cell
does not save; a saving cell records one whole cycle),
``reference_seq`` and ``rate_metric`` (the name the cell's rate is
reported under; ``tokens_per_s`` unless the cell says otherwise).

A *cycle* is one save (if the cell saves) followed by ``save_every``
steps, or one step where it does not. Rates are taken over the whole
cycles that ended inside the window, every one ended by fetching its
last loss; what is left of the window after the last whole cycle is run
and not counted, so a rate does not move with where the window's end
falls in a cycle. The first cycle is always run whole and counted, so a
slow save cannot leave a window without a rate.
"""

from __future__ import annotations

import math
import shutil
import tempfile
import threading
import time

import numpy as np

from benchmarks.harness import stats

SPAN_NAMES = ("batch", "step", "save")
STAGING_THREAD = "ckpt-staging"   # checkpoint/engine.py names it so
# |program loss - plain reference loss| on one seeded batch. bf16
# activations and matmuls over <= 20 layers against float32 "highest":
# the two chip paths PR 22 compared (fused vs chunked CE) differed by
# <= 5e-4 at these widths, so 0.02 leaves room for bf16 rounding and
# none for a dropped term or a path in lower precision than stated.
REFERENCE_TOLERANCE = 0.02
# |first loss - (ln V + dim sigma^2 / 2)|: what random weights give
# (PERF.md, PR 22: 12.54 measured against 12.58)
FIRST_LOSS_TOLERANCE = 0.25


def _staging_thread():
    for t in threading.enumerate():
        if t.name == STAGING_THREAD and t.is_alive():
            return t
    return None


def _stop_shm_tracker():
    """The engine's shm segment makes Python start a resource-tracker
    process; end it and wait for it, so that the run leaves no process
    behind."""
    from multiprocessing import resource_tracker

    tracker = getattr(resource_tracker, "_resource_tracker", None)
    if tracker is not None and getattr(tracker, "_pid", None) is not None:
        tracker._stop()


def _leaf_sums(x):
    """Two wrapping 32-bit sums over a leaf's bits, the second weighted
    by position: equal for equal bytes, and a changed, dropped or moved
    element changes them. Integer arithmetic, so the order the device
    sums in does not matter."""
    import jax
    import jax.numpy as jnp

    bits = {2: jnp.uint16, 4: jnp.uint32}[x.dtype.itemsize]
    u = jax.lax.bitcast_convert_type(x.reshape(-1), bits).astype(jnp.uint32)
    idx = jax.lax.iota(jnp.uint32, u.shape[0])
    weight = idx * jnp.uint32(2654435761) + jnp.uint32(1)
    return jnp.stack([jnp.sum(u, dtype=jnp.uint32),
                      jnp.sum(u * weight, dtype=jnp.uint32)])


def run(ctx) -> dict:
    import jax
    import jax.numpy as jnp

    from dlrover_tpu.checkpoint.checkpointer import Checkpointer
    from dlrover_tpu.parallel import MeshConfig, build_mesh
    from dlrover_tpu.train.trainer import ElasticTrainer, TrainConfig

    log = ctx.log
    p = ctx.cell["params"]
    seq, batch = int(p["seq"]), int(p["batch"])
    save_every = int(p.get("save_every", 0))
    trace_steps = int(p.get("trace_steps", 5))
    ref_seq = int(p.get("reference_seq", 512))
    rate_metric = p.get("rate_metric", "tokens_per_s")
    steps_per_cycle = save_every or 1
    tokens_per_cycle = steps_per_cycle * batch * seq

    mc = MeshConfig(dp=-1, **ctx.config.get("mesh", {})).resolve(
        len(ctx.devices))
    mesh = build_mesh(mc, devices=ctx.devices)
    fam = ctx.family.build(ctx.config, mesh)
    dp = mc.data_parallel_size
    if batch % dp:
        raise ValueError(f"batch {batch} does not divide over {dp} shards")
    tc = TrainConfig(global_batch_size=batch, micro_batch_size=batch // dp)
    trainer = ElasticTrainer(fam.loss_fn, fam.param_specs, mesh, mc, tc)

    k_params, k_ref, k_data = jax.random.split(jax.random.key(ctx.seed), 3)
    t = time.perf_counter()
    params = fam.init_params(k_params)
    state = trainer.init_state(params)
    del params
    jax.block_until_ready(state)
    state_bytes = sum(l.nbytes for l in jax.tree.leaves(state))
    log(f"state: params={fam.param_count} bytes={state_bytes} "
        f"mesh={dict(mesh.shape)} init_s={time.perf_counter() - t:.2f}")

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
    one_step()

    ckpt = ckpt_dir = state_sums = None
    watchers = []
    if save_every:
        # after the compile cache is set (run.py): CheckpointEngine would
        # otherwise put the cache under this per-run directory
        ckpt_dir = tempfile.mkdtemp(prefix="bench_ckpt_")
        ckpt = Checkpointer(ckpt_dir)
        state_sums = jax.jit(
            lambda st: jnp.stack([_leaf_sums(l) for l in jax.tree.leaves(st)])
        )

    saves = []      # per save: stall, drain, whether it joined a live stage

    def one_save():
        nonlocal attempted, failed
        attempted += 1
        sums = state_sums(state)
        joined = _staging_thread() is not None
        try:
            with jax.profiler.TraceAnnotation("save"):
                stall = ckpt.save(step_no, state)
        except Exception:
            failed += 1
            raise
        returned = time.perf_counter()
        rec = {"step": step_no, "stall": stall, "drain": 0.0,
               "joined_previous": joined, "sums": sums,
               "mode": ckpt._engine.last_stage_mode}
        stage = _staging_thread()
        if stage is not None:
            def watch():
                stage.join()
                rec["drain"] = time.perf_counter() - returned
            w = threading.Thread(target=watch, name="bench-drain-watch")
            w.start()
            watchers.append(w)
        saves.append(rec)
        return rec

    def run_cycle(deadline=None):
        """One cycle; returns (whole, seconds, step seconds, save record).
        Past ``deadline`` the cycle is cut at the next step boundary."""
        t0 = time.perf_counter()
        rec = one_save() if save_every else None
        step_s = []
        for i in range(steps_per_cycle):
            step_s.append(one_step())
            if (deadline is not None and i + 1 < steps_per_cycle
                    and time.perf_counter() >= deadline):
                return False, time.perf_counter() - t0, step_s, rec
        return True, time.perf_counter() - t0, step_s, rec

    try:
        if save_every:
            # the first save allocates the shm segment: set-up, waited for
            dev_stats = ctx.devices[0].memory_stats() or {}
            rec = one_save()
            ckpt.wait_staging()
            log(f"set-up save: stall_s={rec['stall']:.3f} mode={rec['mode']} "
                f"bytes_limit={dev_stats.get('bytes_limit')} "
                f"bytes_in_use={dev_stats.get('bytes_in_use')} "
                f"stage_stats={ckpt._engine.last_stage_stats}")
            saves.clear()
            one_step()      # the step after a save runs before the window

        # -- the measured window ------------------------------------------
        attempted = failed = 0
        window_t0 = time.perf_counter()
        setup_s = window_t0 - ctx.t_start
        deadline = window_t0 + ctx.seconds
        cycles = []
        while not cycles or time.perf_counter() < deadline:
            whole, sec, step_s, rec = run_cycle(deadline if cycles else None)
            if whole and (not cycles or time.perf_counter() <= deadline):
                cycles.append((sec, step_s, rec))
        window_s = time.perf_counter() - window_t0
        n_window_saves = len(saves)
        window_attempted, window_failed = attempted, failed

        # -- a traced stretch, after the window, in a run of its own ------
        if ctx.trace_dir:
            if ckpt is not None:
                ckpt.wait_staging()
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 2
            jax.profiler.start_trace(ctx.trace_dir, profiler_options=options)
            try:
                for _ in range(1 if save_every else trace_steps):
                    run_cycle()
            finally:
                jax.profiler.stop_trace()

        # -- the last acknowledged checkpoint, read back ------------------
        readback_ok = True
        if ckpt is not None:
            ckpt.wait_staging()
            for w in watchers:
                w.join()
            readback_ok = _read_back(ckpt, saves[-1], state, log)
    finally:
        if ckpt is not None:
            ckpt.close(unlink_shm=True)
            shutil.rmtree(ckpt_dir, ignore_errors=True)
            _stop_shm_tracker()

    cycle_s = [c[0] for c in cycles]
    step_s = [s for c in cycles for s in c[1]]
    window_saves = saves[:n_window_saves]   # every save the window started
    all_finite = all(math.isfinite(l) for l in losses)
    log(f"window: {len(cycles)} whole cycles, {len(step_s)} steps, "
        f"{n_window_saves} saves "
        f"in {sum(cycle_s):.3f}s of {window_s:.3f}s; last loss "
        f"{losses[-1]:.4f}")

    tokens_per_s = len(cycles) * tokens_per_cycle / sum(cycle_s)
    e2e = {
        rate_metric: (tokens_per_s, "tokens/s"),
        "step_p95_ms": (stats.percentile(step_s, 0.95) * 1e3, "ms"),
        "setup_s": (setup_s, "s"),
    }
    counters = {
        "build_s": build.get("compile_s") or 0.0,
        "step_ms": stats.median(step_s) * 1e3,
        "tokens_per_s": tokens_per_s,
        "flops_per_token": fam.flops_per_token(seq),
        "chips": len(ctx.devices),
    }
    if window_saves:
        counters["save_stall_s"] = sum(
            r["stall"] for r in window_saves) / len(window_saves)
        counters["ckpt_drain_s"] = stats.median(
            [r["drain"] for r in window_saves])
        log(f"saves (first 12 of {len(saves)} started): " + " ".join(
            f"[step={r['step']} stall={r['stall']:.3f} drain={r['drain']:.3f} "
            f"mode={r['mode']} joined_previous={r['joined_previous']}]"
            for r in saves[:12]))
    log(f"step seconds: n={len(step_s)} median={stats.median(step_s):.4f} "
        f"p95={stats.percentile(step_s, 0.95):.4f} max={max(step_s):.4f} "
        f"min={min(step_s):.4f}")
    return {
        "correct": bool(all_finite and first_ok and reference_ok
                        and readback_ok and failed == 0),
        "attempted": window_attempted,
        "failed": window_failed,
        "end_to_end": e2e,
        "counters": counters,
        "span_names": SPAN_NAMES,
    }


def _read_back(ckpt, last_save, state, log) -> bool:
    """Load the last acknowledged checkpoint from the memory tier as host
    arrays and hold every leaf's bytes to the checksum taken on the
    device just before that save. The device cannot hold a second state,
    so the leaves go back one at a time."""
    import jax

    t = time.perf_counter()
    loaded = ckpt.load()
    if loaded is None:
        log("read-back: nothing to load")
        return False
    step, host_state = loaded
    want = np.asarray(last_save["sums"])
    live = jax.tree.leaves(state)
    got_leaves = jax.tree.leaves(host_state)
    if step != last_save["step"] or len(got_leaves) != len(live):
        log(f"read-back: step {step} (saved {last_save['step']}), "
            f"{len(got_leaves)} leaves (saved {len(live)})")
        return False
    leaf_sums = jax.jit(_leaf_sums)
    bad = 0
    for i, (host, like) in enumerate(zip(got_leaves, live)):
        host = np.asarray(host)
        if host.shape != like.shape or host.dtype != like.dtype:
            bad += 1
            continue
        got = np.asarray(leaf_sums(jax.device_put(host, like.sharding)))
        bad += int(not np.array_equal(got, want[i]))
    log(f"read-back: step={step} leaves={len(live)} mismatched={bad} "
        f"s={time.perf_counter() - t:.2f}")
    return bad == 0
