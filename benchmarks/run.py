"""Run one cell of the benchmark once.

    python benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process: it imports JAX itself, builds the cell, warms up, measures
for ``--seconds`` and prints one JSON object as the last line of its
standard output (earlier lines are free). This file knows no model, no
cell and no metric by name: a cell is ``workloads/<cell>.json``, which
names a configuration (``configs/<config>.json``, whose ``family`` names
``families/<family>.py``) and a job (``jobs/<job>.py``); a per-layer
metric is ``layer_metrics/<name>.json`` with a reader. Which metrics a
cell reports is what ``BENCHMARK.json`` says; a cell it does not list (a
rehearsal cell) reports whatever its job and the readers give.

It fails unless JAX's backend is the TPU. ``JAX_PLATFORMS=cpu``, set
explicitly, is the rehearsal mode: the line then says ``platform: cpu``
and no number of it is a device number.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import types  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def log(msg: str):
    print(f"[bench {time.perf_counter() - T_START:8.2f}s] {msg}", flush=True)


def _load_json(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def _load_module(*parts):
    path = os.path.join(HERE, *parts)
    name = "benchmarks." + ".".join(parts)[: -len(".py")]
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _listed(entries, cell: str, benchmark):
    """Names of the metrics ``BENCHMARK.json`` gives this cell, or None
    where it does not list the cell (then nothing is filtered)."""
    if benchmark is None or cell not in {
        w["name"] for w in benchmark["workloads"]
    }:
        return None
    return {
        m["name"] for m in benchmark[entries]
        if "workloads" not in m or cell in m["workloads"]
    }


def _layer_metrics(ctx) -> dict:
    from benchmarks.harness import readers

    out = {}
    folder = os.path.join(HERE, "layer_metrics")
    for fname in sorted(os.listdir(folder)):
        if not fname.endswith(".json"):
            continue
        name = fname[: -len(".json")]
        if ctx.wanted is not None and name not in ctx.wanted:
            continue
        spec = _load_json("layer_metrics", fname)
        if os.path.exists(os.path.join(folder, name + ".py")):
            read = _load_module("layer_metrics", name + ".py").read
        else:
            read = getattr(readers, spec["reader"])
        value = read(spec, ctx)
        if value is not None:
            out[name] = {"value": float(value), "unit": spec["unit"]}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default="",
                    help="copy the traced run's .xplane.pb here (by hand)")
    args = ap.parse_args(argv)

    sys.path.insert(0, ROOT)
    cell = _load_json("workloads", args.workload + ".json")
    config = _load_json("configs", cell["config"] + ".json")
    benchmark = None
    if os.path.exists(os.path.join(ROOT, "BENCHMARK.json")):
        with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
            benchmark = json.load(f)

    # JAX's persistent compile cache, at a fixed place inside the
    # checkout unless the environment gives one; set before anything of
    # the program can choose another (CheckpointEngine would otherwise
    # put it under its per-run checkpoint directory)
    os.environ.setdefault(
        "JAX_COMPILATION_CACHE_DIR", os.path.join(ROOT, ".jax_cache"))
    # or libtpu writes its logs to a fixed directory under /tmp
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # shm segments are keyed by the job's name and the host shares them
    os.environ["DLROVER_TPU_JOB_NAME"] = f"bench{os.getpid()}"

    import jax

    # small programs (init, batch, checksums) are cached too, so that a
    # second run compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

    rehearsal = os.environ.get("JAX_PLATFORMS", "") == "cpu"
    backend = jax.default_backend()
    if backend != "tpu" and not rehearsal:
        print(f"no TPU: JAX's backend is {backend!r} (JAX_PLATFORMS=cpu "
              "rehearses)", file=sys.stderr)
        return 3
    chips = int(config["chips"])
    if jax.device_count() < chips:
        print(f"{args.workload} needs {chips} chips, JAX sees "
              f"{jax.device_count()}", file=sys.stderr)
        return 3
    devices = jax.devices()[:chips]
    log(f"cell={args.workload} config={cell['config']} seed={args.seed} "
        f"seconds={args.seconds} trace={args.trace} platform="
        f"{devices[0].platform} kind={devices[0].device_kind!r} "
        f"count={jax.device_count()} cache="
        f"{jax.config.jax_compilation_cache_dir}")

    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if args.trace else ""
    ctx = types.SimpleNamespace(
        cell=cell, config=config, seed=args.seed, seconds=args.seconds,
        t_start=T_START, devices=devices, trace_dir=trace_dir, log=log,
        family=_load_module("families", config["family"] + ".py"),
        wanted=_listed("per_layer", args.workload, benchmark),
    )
    try:
        result = _load_module("jobs", cell["job"] + ".py").run(ctx)
        line = {
            "correct": result["correct"],
            "attempted": result["attempted"],
            "failed": result["failed"],
        }
        peak = max(
            (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
            for d in devices
        )
        line["device"] = {
            "platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": jax.device_count(),
            "memory_peak_bytes": int(peak),
        }
        if args.trace:
            from benchmarks.harness import trace_reduce

            path = trace_reduce.find_xplane(trace_dir)
            if path is None:
                raise RuntimeError(f"the profiler wrote no trace to {trace_dir}")
            log(f"trace: {path} {os.path.getsize(path)} bytes")
            if args.keep_trace:
                os.makedirs(args.keep_trace, exist_ok=True)
                shutil.copy(path, args.keep_trace)
            ctx.trace = trace_reduce.load(path, result["span_names"])
            ctx.counters = result["counters"]
            busy_s, window_s = trace_reduce.busy_and_window_s(ctx.trace)
            line["device"]["busy_s"] = busy_s
            line["device"]["window_s"] = window_s
            line["metrics"] = _layer_metrics(ctx)
            line["breakdown"] = {
                "device_ops": trace_reduce.top_ops(ctx.trace),
                "idle_gaps": trace_reduce.idle_gaps(ctx.trace),
            }
        else:
            wanted = _listed("end_to_end", args.workload, benchmark)
            line["metrics"] = {
                name: {"value": float(value), "unit": unit}
                for name, (value, unit) in result["end_to_end"].items()
                if wanted is None or name in wanted
            }
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
