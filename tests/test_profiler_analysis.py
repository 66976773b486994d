"""Profiler analysis tooling: stack trie, timeline stats, matmul replay.

Parity: reference ``py_xpu_timer`` tests its stack viewer and timeline
tooling offline against canned artifacts; same approach here.
"""

import json

from dlrover_tpu.profiler.analysis import (
    StackTrie,
    analyze_timeline,
    load_stacks,
    matmul_bench,
    parse_faulthandler,
)

DUMP_RANK0 = """\
Current thread 0x00007f11 (most recent call first):
  File "/app/dlrover_tpu/ops/ring_attention.py", line 88 in _ring_step
  File "/app/train.py", line 40 in train_step
  File "/app/train.py", line 80 in main
Thread 0x00007f22 (most recent call first):
  File "/usr/lib/python3.11/threading.py", line 320 in wait
  File "/app/dlrover_tpu/checkpoint/engine.py", line 100 in _stage_loop
"""

DUMP_RANK1 = """\
Thread 0x00007f33 (most recent call first):
  File "/app/dlrover_tpu/ops/ring_attention.py", line 88 in _ring_step
  File "/app/train.py", line 40 in train_step
  File "/app/train.py", line 80 in main
"""


def test_parse_faulthandler_orders_root_first():
    stacks = parse_faulthandler(DUMP_RANK0)
    assert len(stacks) == 2
    # root-first: entry point at index 0, innermost frame last
    assert stacks[0][0].startswith("main (train.py:80")
    assert stacks[0][-1].startswith("_ring_step (ring_attention.py:88")


def test_stack_trie_merges_shared_hang_path():
    trie = StackTrie()
    trie.add_dump(DUMP_RANK0)
    trie.add_dump(DUMP_RANK1)
    # both ranks share main -> train_step -> _ring_step; the checkpoint
    # thread is a 1-weight side branch
    hot = trie.hot_path()
    assert hot[-1].startswith("_ring_step")
    rendered = trie.render(min_share=0.0)
    assert "   2  66.7%  main (train.py:80)" in rendered
    assert "_stage_loop" in rendered


def test_load_stacks_from_bundle_json(tmp_path):
    bundle = {"stacks": {"101": DUMP_RANK0, "102": DUMP_RANK1}}
    p = tmp_path / "bundle.json"
    p.write_text(json.dumps(bundle))
    # main_only: rank0's Current thread + rank1's fallback (no Current
    # marker -> non-idle stacks); rank0's idle checkpoint waiter dropped
    trie = load_stacks(str(p))
    assert trie.total == 2
    assert trie.hot_path()[-1].startswith("_ring_step")


def test_load_stacks_from_dir(tmp_path):
    (tmp_path / "hang_stacks-101.txt").write_text(DUMP_RANK0)
    (tmp_path / "hang_stacks-102.txt").write_text(DUMP_RANK1)
    trie = load_stacks(str(tmp_path))
    assert trie.total == 2
    assert trie.hot_path()[-1].startswith("_ring_step")


def test_analyze_timeline_stats_occupancy_and_gaps():
    events = [
        # two executes back to back, then a 500us host stall, then another
        {"name": "jit_step", "cat": "execute", "ph": "X", "ts": 0, "dur": 100},
        {"name": "jit_step", "cat": "execute", "ph": "X", "ts": 100, "dur": 100},
        {"name": "jit_step", "cat": "execute", "ph": "X", "ts": 700, "dur": 200},
        {"name": "jit_step", "cat": "compile", "ph": "X", "ts": 0, "dur": 50},
    ]
    rep = analyze_timeline(events)
    ex = rep["programs"]["execute:jit_step"]
    assert ex["count"] == 3 and ex["total_us"] == 400
    # busy 400us over a 900us wall
    assert abs(rep["device_occupancy"] - 400 / 900) < 1e-4
    assert rep["top_gaps"][0]["gap_us"] == 500
    assert "compile:jit_step" in rep["programs"]


def test_matmul_bench_runs_on_any_backend():
    rep = matmul_bench(64, 64, 64, dtype="float32", iters=2)
    assert rep["achieved_gflops"] > 0
    assert rep["time_us"] > 0


def test_cli_stacks_and_timeline(tmp_path, capsys):
    from dlrover_tpu.profiler.analysis import main

    bundle = tmp_path / "bundle.json"
    bundle.write_text(json.dumps({"stacks": {"1": DUMP_RANK1}}))
    assert main(["stacks", str(bundle)]) == 0
    out = capsys.readouterr().out
    assert "hot path leaf" in out and "_ring_step" in out

    tl = tmp_path / "timeline.json"
    tl.write_text(json.dumps({"traceEvents": [
        {"name": "p", "cat": "execute", "ph": "X", "ts": 0, "dur": 10},
    ]}))
    assert main(["timeline", str(tl)]) == 0
    assert "device_occupancy" in capsys.readouterr().out


def test_stack_sampler_finds_hotspot():
    """In-process sampler (reference stack_util.cc): a busy function
    dominates the sampled trie."""
    import time

    from dlrover_tpu.profiler.stack_sampler import StackSampler

    def hot_spin(until):
        while time.time() < until:
            sum(range(200))

    with StackSampler(interval=0.002) as s:
        hot_spin(time.time() + 0.4)
    assert s.samples > 20
    hot = s.hot_path()
    assert any("hot_spin" in fr for fr in hot), hot
    assert "hot_spin" in s.render(min_share=0.3)


def test_stack_sampler_dump(tmp_path):
    import time

    from dlrover_tpu.profiler.stack_sampler import StackSampler, profile_block

    s = profile_block(0.1, interval=0.005)
    p = tmp_path / "hot.txt"
    s.dump(str(p))
    text = p.read_text()
    assert "samples @" in text


def test_stack_sampler_ignores_parked_pool_threads():
    """ADVICE r3: idle thread-pool workers must not outweigh the busy
    thread — hot_path() names the hotspot even with a parked executor
    in-process (the state every real JAX worker is in)."""
    import concurrent.futures
    import time

    from dlrover_tpu.profiler.stack_sampler import StackSampler

    def hot_spin(until):
        while time.time() < until:
            sum(range(200))

    pool = concurrent.futures.ThreadPoolExecutor(max_workers=4)
    # Materialize the worker threads, then leave them parked on queue.get.
    for _ in pool.map(lambda x: x, range(4)):
        pass
    try:
        with StackSampler(interval=0.002) as s:
            hot_spin(time.time() + 0.4)
        hot = s.hot_path()
        assert any("hot_spin" in fr for fr in hot), hot
        assert not any("_worker" in fr for fr in hot), hot
    finally:
        pool.shutdown(wait=False)


def test_stack_sampler_ignores_a_thread_blocked_in_a_read():
    """A receiver thread parked in a blocking read of its pipe (as an
    RPC library's is: the whole of its stack the same in every sample)
    is not the hotspot."""
    import os
    import threading
    import time

    from dlrover_tpu.profiler.stack_sampler import StackSampler

    def hot_spin(until):
        while time.time() < until:
            sum(range(200))

    r, w = os.pipe()

    def read():
        os.read(r, 1)

    receiver = threading.Thread(target=read, daemon=True)
    receiver.start()
    try:
        with StackSampler(interval=0.002) as s:
            hot_spin(time.time() + 0.4)
        hot = s.hot_path()
        assert any("hot_spin" in fr for fr in hot), hot
        assert " read (" not in s.render(min_share=0.0)
    finally:
        os.write(w, b"x")
        receiver.join()
        os.close(r)
        os.close(w)


def test_hang_trie_main_thread_only():
    """ADVICE r3: hang-dump summarization weights only the 'Current
    thread' section so stuck_at names the hung collective, not an idle
    helper frame replicated across every worker."""
    from dlrover_tpu.profiler.analysis import StackTrie, is_idle_stack

    dump = "\n".join(
        [
            'Thread 0x01 (most recent call first):',
            '  File "queue.py", line 171 in get',
            '  File "thread.py", line 90 in _worker',
            '  File "threading.py", line 975 in run',
            'Thread 0x02 (most recent call first):',
            '  File "queue.py", line 171 in get',
            '  File "thread.py", line 90 in _worker',
            '  File "threading.py", line 975 in run',
            'Current thread 0x03 (most recent call first):',
            '  File "comm.py", line 12 in psum',
            '  File "train.py", line 44 in step',
        ]
    )
    trie = StackTrie()
    # Two workers, each with 2 idle helper threads + 1 stuck main thread.
    trie.add_dump(dump, main_only=True)
    trie.add_dump(dump, main_only=True)
    hot = trie.hot_path()
    assert hot and "psum" in hot[-1], hot

    assert is_idle_stack(["run (threading.py:975)", "_worker (thread.py:90)",
                          "get (queue.py:171)"])
    assert not is_idle_stack(["step (train.py:44)", "psum (comm.py:12)"])
