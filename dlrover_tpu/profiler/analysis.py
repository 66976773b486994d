"""Offline analysis tooling over profiler artifacts.

Parity: the reference ships a ``py_xpu_timer`` toolbox next to its native
profiler — a stack-trie viewer for all-rank stacktrace dumps
(``xpu_timer/py_xpu_timer/py_xpu_timer/stack_viewer.py:21-132``), matmul
timing analysis/replay (``parse_matmul.py``) and NCCL collective analysis.
TPU-natively the inputs differ (faulthandler stack dumps from
``profiler.hang_dump``, chrome-trace timelines and per-program Prometheus
counters from ``native/tpu_timer``), but the questions are the same:

- **Where is everyone stuck?** Merge every rank's Python stacks into a
  trie; a hang shows up as one deep shared path with ``n_ranks`` weight.
- **What is the device doing?** Per-program duration stats, device
  occupancy, and the largest execution gaps (host-bound stalls) from the
  chrome-trace timeline.
- **How fast SHOULD this matmul be?** Replay an (M, K, N) matmul on the
  live backend and report achieved vs peak FLOPs — the reference's replay
  tool rebuilt CUDA GEMMs; here XLA compiles the same HLO the trainer hits.

CLI::

    python -m dlrover_tpu.profiler.analysis stacks <bundle.json | dir>
    python -m dlrover_tpu.profiler.analysis timeline <timeline.json>
    python -m dlrover_tpu.profiler.analysis matmul-bench M K N [--dtype bfloat16]
"""

from __future__ import annotations

import json
import os
import re
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Tuple

# ---------------------------------------------------------------------------
# Stack trie (reference stack_viewer.py)
# ---------------------------------------------------------------------------

#: one faulthandler frame: `  File "x.py", line 10 in foo`
_FRAME_RE = re.compile(r'^\s*File "(?P<file>[^"]+)", line (?P<line>\d+) in (?P<func>.+)$')
_THREAD_RE = re.compile(r"^(Current thread|Thread) (?P<tid>0x[0-9a-fA-F]+)")


def parse_faulthandler(text: str, main_only: bool = False) -> List[List[str]]:
    """Parse faulthandler output into stacks, one per thread, each a list
    of ``func (file:line)`` frames ordered root-first (faulthandler prints
    most-recent-call-first; we reverse so the trie roots at the entry
    point, like a flamegraph).

    ``main_only`` keeps just the "Current thread" section — in a hang
    dump the main thread is the one parked in the collective, while each
    worker process carries several identical idle helper threads that
    would otherwise outweigh it in the trie.
    """
    stacks: List[List[str]] = []
    cur: Optional[List[str]] = None
    cur_is_main = False
    any_main = False

    def flush():
        if cur and (cur_is_main or not main_only):
            stacks.append(list(reversed(cur)))

    for line in text.splitlines():
        m_thread = _THREAD_RE.match(line)
        if m_thread:
            flush()
            cur = []
            cur_is_main = line.startswith("Current thread")
            any_main = any_main or cur_is_main
            continue
        m = _FRAME_RE.match(line)
        if m and cur is not None:
            short = os.path.basename(m.group("file"))
            cur.append(f"{m.group('func')} ({short}:{m.group('line')})")
    flush()
    if main_only and not any_main:
        # Dump without a "Current thread" marker: fall back to every
        # non-idle stack rather than returning nothing.
        return [s for s in parse_faulthandler(text) if not is_idle_stack(s)]
    return stacks


#: leaf frames of threads that are parked, not working: thread-pool
#: workers waiting on their queue, threading waits, selector polls, and a
#: receiver blocked in a read of its socket, pipe or file (the C call has
#: no Python frame, so the leaf is the wrapper that made it, whose name
#: is the rule: an RPC library's is in no file known here).
#: Leaf-only on purpose — an executor thread actively running a task has
#: deeper frames (``_worker -> run -> fn``) and must stay visible; a
#: parked one is blocked in the C-level queue get, so its deepest
#: *Python* frame is ``_worker`` itself.
_IDLE_LEAF_RE = re.compile(
    r"^(wait|_wait_for_tstate_lock|_recv_bytes|poll|select|accept|"
    r"get|_get_block) \((threading|queue|selectors|socket|connection)\.py:"
    r"|^(read|readinto|readline|recv|recv_into) \("
    r"|^_worker \(thread\.py:"
    r"|^worker \(pool\.py:"
)


def is_idle_stack(frames: List[str]) -> bool:
    """True if a root-first stack belongs to a parked helper thread
    (thread-pool worker waiting for work, selector loop, queue get) —
    the stacks that drown out the busy thread when every thread is
    sampled with equal weight."""
    if not frames:
        return True
    return bool(_IDLE_LEAF_RE.match(frames[-1]))


@dataclass
class _TrieNode:
    weight: int = 0
    children: Dict[str, "_TrieNode"] = field(default_factory=dict)


class StackTrie:
    """Merge many ranks' stacks; shared prefixes accumulate weight so the
    dominant (stuck) path is the heaviest branch."""

    def __init__(self):
        self._root = _TrieNode()
        self.total = 0

    def insert(self, frames: List[str], weight: int = 1):
        self.total += weight
        node = self._root
        node.weight += weight
        for fr in frames:
            node = node.children.setdefault(fr, _TrieNode())
            node.weight += weight

    def add_dump(self, text: str, weight: int = 1, main_only: bool = False):
        for stack in parse_faulthandler(text, main_only=main_only):
            self.insert(stack, weight)

    def render(self, min_share: float = 0.05, _node=None, _depth=0) -> str:
        """Indented trie, heaviest children first, pruned below
        ``min_share`` of the total weight."""
        node = _node or self._root
        lines: List[str] = []
        if _depth == 0 and self.total == 0:
            return "<no stacks>"
        for name, child in sorted(
            node.children.items(), key=lambda kv: -kv[1].weight
        ):
            if child.weight < min_share * self.total:
                continue
            pct = 100.0 * child.weight / self.total
            lines.append(f"{'  ' * _depth}{child.weight:4d} {pct:5.1f}%  {name}")
            sub = self.render(min_share, child, _depth + 1)
            if sub:
                lines.append(sub)
        return "\n".join(l for l in lines if l)

    def hot_path(self) -> List[str]:
        """The single heaviest root-to-leaf path — for a collective hang
        this is the frame every rank is parked in."""
        path: List[str] = []
        node = self._root
        while node.children:
            name, node = max(node.children.items(), key=lambda kv: kv[1].weight)
            path.append(name)
        return path


def load_stacks(path: str) -> StackTrie:
    """Build a trie from a hang bundle JSON (``HangDumper.dump`` output:
    ``{"stacks": {pid: text}}``) or a directory of ``hang_stacks-*.txt``."""
    trie = StackTrie()
    if os.path.isdir(path):
        for fn in sorted(os.listdir(path)):
            if fn.startswith("hang_stacks-"):
                with open(os.path.join(path, fn)) as f:
                    trie.add_dump(f.read(), main_only=True)
    else:
        with open(path) as f:
            bundle = json.load(f)
        for text in bundle.get("stacks", {}).values():
            trie.add_dump(text, main_only=True)
    return trie


# ---------------------------------------------------------------------------
# Timeline analysis (reference parse_matmul.py / NCCL analysis, TPU-shaped)
# ---------------------------------------------------------------------------


def analyze_timeline(events: Iterable[Dict]) -> Dict:
    """Chrome-trace "X" events -> per-program stats + device occupancy +
    largest inter-execution gaps (host-bound stalls: the device idles while
    Python/dispatch catches up)."""
    per: Dict[str, List[int]] = {}
    spans: List[Tuple[int, int]] = []  # (start, end) us, execute events only
    for ev in events:
        if ev.get("ph") != "X":
            continue
        name, dur = ev.get("name", "?"), int(ev.get("dur", 0))
        per.setdefault(f"{ev.get('cat', '?')}:{name}", []).append(dur)
        if ev.get("cat") == "execute":
            ts = int(ev.get("ts", 0))
            spans.append((ts, ts + dur))

    programs = {}
    total_us = sum(sum(v) for v in per.values()) or 1
    for name, durs in sorted(per.items(), key=lambda kv: -sum(kv[1])):
        durs.sort()
        n = len(durs)
        programs[name] = {
            "count": n,
            "total_us": sum(durs),
            "share": round(sum(durs) / total_us, 4),
            "mean_us": round(sum(durs) / n, 1),
            "p50_us": durs[n // 2],
            "p99_us": durs[min(n - 1, int(n * 0.99))],
        }

    occupancy, gaps = 0.0, []
    if spans:
        spans.sort()
        wall = spans[-1][1] - spans[0][0]
        busy, cur_s, cur_e = 0, spans[0][0], spans[0][0]
        for s, e in spans:
            if s > cur_e:  # device idle between executions
                gaps.append({"at_us": cur_e, "gap_us": s - cur_e})
                busy += cur_e - cur_s
                cur_s, cur_e = s, e
            else:
                cur_e = max(cur_e, e)
        busy += cur_e - cur_s
        occupancy = busy / wall if wall else 1.0
        gaps.sort(key=lambda g: -g["gap_us"])
    return {
        "programs": programs,
        "device_occupancy": round(occupancy, 4),
        "top_gaps": gaps[:10],
    }


def analyze_timeline_file(path: str) -> Dict:
    with open(path) as f:
        doc = json.load(f)
    return analyze_timeline(doc.get("traceEvents", []))


# ---------------------------------------------------------------------------
# job-timeline: merge every rank's trace-spine dump + the master's
# events (+ interposer /timeline dumps) into ONE perfetto-loadable file
# ---------------------------------------------------------------------------


def validate_trace_events(events, label: str = "") -> List[str]:
    """Structural validation of chrome-trace events: required fields,
    non-negative durations, and — per (pid, tid) lane — proper nesting
    of complete ("X") spans. Two spans on one lane must either be
    disjoint or fully contained; a partial overlap means a broken clock
    basis or a torn emitter, which would render as garbage in perfetto
    and silently corrupt any attribution derived from the file."""
    errors: List[str] = []
    lanes: Dict[Tuple, List[Tuple[float, float, str]]] = {}
    for i, ev in enumerate(events):
        if not isinstance(ev, dict):
            errors.append(f"{label}: event #{i} is not an object")
            continue
        ph = ev.get("ph")
        if ph == "M":
            continue  # metadata events carry no clock
        ts = ev.get("ts")
        if not isinstance(ts, (int, float)):
            errors.append(f"{label}: event #{i} ({ev.get('name')!r}) has "
                          f"non-numeric ts {ts!r}")
            continue
        if ph == "X":
            dur = ev.get("dur", 0)
            if not isinstance(dur, (int, float)) or dur < 0:
                errors.append(
                    f"{label}: span #{i} ({ev.get('name')!r}) has invalid "
                    f"dur {dur!r}"
                )
                continue
            lanes.setdefault((ev.get("pid"), ev.get("tid")), []).append(
                (float(ts), float(ts) + float(dur), str(ev.get("name")))
            )
    tol = 1.0  # one microsecond of rounding slack
    for (pid, tid), spans in lanes.items():
        spans.sort(key=lambda s: (s[0], -(s[1] - s[0])))
        stack: List[Tuple[float, float, str]] = []
        for s, e, name in spans:
            while stack and s >= stack[-1][1] - tol:
                stack.pop()
            if stack and e > stack[-1][1] + tol:
                errors.append(
                    f"{label}: lane (pid={pid}, tid={tid}): span {name!r} "
                    f"[{s:.0f},{e:.0f}]us partially overlaps "
                    f"{stack[-1][2]!r} [{stack[-1][0]:.0f},"
                    f"{stack[-1][1]:.0f}]us"
                )
            stack.append((s, e, name))
    return errors


def _load_trace_file(path: str):
    """-> (events, meta, errors). Accepts trace-spine dumps (``dlrover``
    metadata block, epoch-us clock), raw chrome-trace docs and bare
    event arrays (interposer ``/timeline`` dumps)."""
    try:
        with open(path) as f:
            doc = json.load(f)
    except (OSError, ValueError) as e:
        return [], {}, [f"{os.path.basename(path)}: unparseable ({e})"]
    if isinstance(doc, list):
        events, meta = doc, {}
    elif isinstance(doc, dict):
        events = doc.get("traceEvents", [])
        meta = doc.get("dlrover", {}) or {}
        if not isinstance(events, list):
            return [], meta, [
                f"{os.path.basename(path)}: traceEvents is not a list"
            ]
    else:
        return [], {}, [f"{os.path.basename(path)}: not a trace document"]
    return events, meta, []


def merge_job_timeline(paths: List[str]) -> Tuple[Dict, List[str]]:
    """Merge per-role trace dumps into one chrome-trace document.

    Sources carrying the spine's ``dlrover.clock == "epoch_us"``
    metadata already share an absolute clock (NTP across hosts) and
    merge as-is. Sources without it (interposer dumps: raw monotonic
    microseconds) are re-based so their first event aligns with the
    earliest epoch-clock event — best-effort, flagged in the source
    table. Every file becomes its own pid with a ``process_name``
    metadata row, so perfetto shows one track group per rank/role.
    """
    loaded = []
    errors: List[str] = []
    for path in sorted(paths):
        events, meta, errs = _load_trace_file(path)
        errors.extend(errs)
        if errs:
            continue
        loaded.append((os.path.basename(path), events, meta))
    epoch_min = None
    for _, events, meta in loaded:
        if meta.get("clock") == "epoch_us":
            for ev in events:
                ts = ev.get("ts")
                if isinstance(ts, (int, float)):
                    epoch_min = ts if epoch_min is None else min(epoch_min, ts)
    merged: List[Dict] = []
    sources = []
    for pid, (name, events, meta) in enumerate(loaded):
        offset = 0.0
        aligned = meta.get("clock") == "epoch_us"
        if not aligned and epoch_min is not None:
            first = min(
                (ev["ts"] for ev in events
                 if isinstance(ev.get("ts"), (int, float))),
                default=None,
            )
            if first is not None:
                offset = epoch_min - first
        role = meta.get("role") or os.path.splitext(name)[0]
        label = role
        if meta.get("node_id") is not None:
            label += f"-n{meta['node_id']}"
        if meta.get("process_id") is not None:
            label += f"-p{meta['process_id']}"
        merged.append({
            "name": "process_name", "ph": "M", "pid": pid, "tid": 0,
            "args": {"name": label},
        })
        n = 0
        for ev in events:
            if not isinstance(ev, dict):
                continue
            ev = dict(ev)
            ev["pid"] = pid
            if isinstance(ev.get("ts"), (int, float)):
                ev["ts"] = ev["ts"] + offset
            merged.append(ev)
            n += 1
        sources.append({
            "file": name, "pid": pid, "label": label, "events": n,
            "clock": "epoch_us" if aligned else
            ("rebased" if offset else "unaligned"),
        })
        errors.extend(validate_trace_events(events, label=name))
    merged.sort(key=lambda ev: (ev.get("ts") is not None,
                                ev.get("ts") or 0))
    doc = {
        "traceEvents": merged,
        "displayTimeUnit": "ms",
        "dlrover": {"merged_from": sources},
    }
    return doc, errors


def job_timeline_paths(target: str) -> List[str]:
    """Expand one CLI operand: a directory yields every ``*.json``
    inside it (the trace-spine dump dir), a file is itself."""
    if os.path.isdir(target):
        return [
            os.path.join(target, fn)
            for fn in sorted(os.listdir(target))
            if fn.endswith(".json")
        ]
    return [target]


# ---------------------------------------------------------------------------
# Matmul replay microbench (reference matmul replay, XLA-shaped)
# ---------------------------------------------------------------------------


def matmul_bench(m: int, k: int, n: int, dtype: str = "bfloat16",
                 iters: int = 20) -> Dict:
    """Time C[m,n] = A[m,k] @ B[k,n] on the live backend; report achieved
    FLOPs and, on TPU, the fraction of the chip's peak — is this shape
    MXU-friendly or is something (layout, small dims) leaving it on the
    table?"""
    import jax
    import jax.numpy as jnp

    from dlrover_tpu.utils.tpu_info import peak_bf16_flops

    dt = jnp.dtype({"bf16": "bfloat16", "f32": "float32",
                    "f16": "float16"}.get(dtype, dtype))
    a = jax.random.normal(jax.random.key(0), (m, k), jnp.float32).astype(dt)
    b = jax.random.normal(jax.random.key(1), (k, n), jnp.float32).astype(dt)
    f = jax.jit(lambda a, b: a @ b)
    import time

    jax.block_until_ready(f(a, b))  # compile
    t0 = time.perf_counter()
    out = None
    for _ in range(iters):
        out = f(a, b)
    # the matmuls queue on one device stream: the last one done, all done
    jax.block_until_ready(out)
    dt_s = max(time.perf_counter() - t0, 1e-9) / iters
    achieved = 2.0 * m * k * n / dt_s
    dev = jax.devices()[0]
    # the peak table is dense-bf16; comparing another dtype against it
    # would answer the MXU-efficiency question wrongly
    peak = peak_bf16_flops(getattr(dev, "device_kind", ""))
    is_bf16 = dt == jnp.bfloat16
    return {
        "m": m, "k": k, "n": n, "dtype": str(dt),
        "backend": jax.default_backend(),
        "time_us": round(dt_s * 1e6, 1),
        "achieved_gflops": round(achieved / 1e9, 2),
        "achieved_tflops": round(achieved / 1e12, 3),
        "pct_peak": (round(achieved / peak, 4)
                     if peak and is_bf16 else None),
    }


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    import argparse

    p = argparse.ArgumentParser("dlrover-tpu-analysis")
    sub = p.add_subparsers(dest="cmd", required=True)
    ps = sub.add_parser("stacks", help="stack-trie view of a hang dump")
    ps.add_argument("path")
    ps.add_argument("--min-share", type=float, default=0.05)
    pt = sub.add_parser("timeline", help="per-program stats from a timeline")
    pt.add_argument("path")
    pj = sub.add_parser(
        "job-timeline",
        help="merge all ranks' trace-spine dumps + master events (+ "
             "interposer timelines) into one perfetto-loadable trace",
    )
    pj.add_argument(
        "paths", nargs="+",
        help="trace dump dirs and/or files (a dir expands to its *.json)",
    )
    pj.add_argument("-o", "--output", default="job_timeline.json")
    pj.add_argument(
        "--check", action="store_true",
        help="exit 1 on unparseable sources or overlap-invalid spans "
             "(CI gate over the chaos e2e artifacts)",
    )
    pm = sub.add_parser("matmul-bench", help="replay an (M,K,N) matmul")
    pm.add_argument("m", type=int)
    pm.add_argument("k", type=int)
    pm.add_argument("n", type=int)
    pm.add_argument("--dtype", default="bfloat16")
    pm.add_argument("--iters", type=int, default=20)
    pm.add_argument(
        "--platform", default="",
        help="force a jax platform (e.g. cpu) — set via jax.config, which "
             "wins over JAX_PLATFORMS",
    )
    args = p.parse_args(argv)

    if getattr(args, "platform", ""):
        import jax

        jax.config.update("jax_platforms", args.platform)

    if args.cmd == "stacks":
        trie = load_stacks(args.path)
        print(trie.render(min_share=args.min_share))
        hot = trie.hot_path()
        if hot:
            print(f"\nhot path leaf: {hot[-1]}")
    elif args.cmd == "timeline":
        print(json.dumps(analyze_timeline_file(args.path), indent=2))
    elif args.cmd == "job-timeline":
        files: List[str] = []
        for target in args.paths:
            files.extend(job_timeline_paths(target))
        if not files:
            print(f"job-timeline: no trace files under {args.paths}")
            return 1
        doc, errors = merge_job_timeline(files)
        with open(args.output, "w") as f:
            json.dump(doc, f)
        srcs = doc["dlrover"]["merged_from"]
        print(
            f"job-timeline: merged {len(srcs)} source(s), "
            f"{sum(s['events'] for s in srcs)} events -> {args.output}"
        )
        for s in srcs:
            print(f"  pid {s['pid']}: {s['label']} ({s['file']}, "
                  f"{s['events']} events, clock={s['clock']})")
        if errors:
            for e in errors:
                print(f"  INVALID: {e}")
            if args.check:
                return 1
        return 0
    else:
        print(json.dumps(
            matmul_bench(args.m, args.k, args.n, args.dtype, args.iters)
        ))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
