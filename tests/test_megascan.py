"""MegaScan tests: tracing pipeline end-to-end + slow-chip detector.

Mirrors the reference validation flow (DockerUsage.md: downclock GPU 0 →
detector flags it; here a synthetic slow process is injected into the
records — SURVEY §4 'synthetic slow chip injection')."""

import json
import os

import numpy as np
import pytest

from megatronapp_tpu.trace.aggregate import (
    aggregate_benchmark_data, aggregate_dir, chrome_trace,
    transform_to_complete_events,
)
from megatronapp_tpu.trace.dependency import amend_p2p, build_dependencies
from megatronapp_tpu.trace.detect import detect_stage1, try_detect


def make_records(pid, iteration, phases, t0=0.0):
    """Synthesize B/E records for one process, one iteration."""
    recs = [{"name": "iteration", "ph": "B", "ts": 0.0, "pid": pid,
             "tid": 0, "iteration": iteration, "args": {}}]
    t = t0
    for name, dur, args in phases:
        recs.append({"name": name, "ph": "B", "ts": t, "pid": pid, "tid": 0,
                     "iteration": iteration, "args": dict(args)})
        t += dur
        recs.append({"name": name, "ph": "E", "ts": t, "pid": pid, "tid": 0,
                     "iteration": iteration, "args": dict(args)})
        t += 1.0
    recs.append({"name": "iteration", "ph": "E", "ts": t, "pid": pid,
                 "tid": 0, "iteration": iteration, "args": {}})
    return recs


class TestAggregation:
    def test_be_to_x_and_stitching(self):
        per_process = {
            0: make_records(0, 0, [("forward", 10, {}), ("backward", 20, {})])
             + make_records(0, 1, [("forward", 12, {}), ("backward", 21, {})]),
            1: make_records(1, 0, [("forward", 11, {}), ("backward", 19, {})])
             + make_records(1, 1, [("forward", 10, {}), ("backward", 22, {})]),
        }
        merged = aggregate_benchmark_data(per_process)
        events = transform_to_complete_events(merged)
        xs = [e for e in events if e["ph"] == "X"]
        assert len(xs) == 2 * 2 * 3  # 2 pids x 2 iters x (fwd,bwd,iteration)
        # iteration 1 events start after iteration 0's max span on all pids.
        it0_max_end = max(e["ts"] + e["dur"] for e in xs
                          if e["args"]["iteration"] == 0)
        it1_min_start = min(e["ts"] for e in xs
                            if e["args"]["iteration"] == 1)
        assert it1_min_start >= it0_max_end - 1e-6
        trace = chrome_trace(xs)
        names = [m for m in trace["traceEvents"] if m.get("ph") == "M"]
        assert len(names) == 4  # process_name + sort_index per pid

    def test_dependency_matching(self):
        phases = [("all-reduce", 5, {"group": [0, 1]}),
                  ("all-reduce", 7, {"group": [0, 1]})]
        per_process = {0: make_records(0, 0, phases),
                       1: make_records(1, 0, phases)}
        merged = aggregate_benchmark_data(per_process)
        events = transform_to_complete_events(merged)
        related = build_dependencies(events)
        ars = [e for e in events if e["name"] == "all-reduce"]
        assert len(ars) == 4
        # Each event is related to exactly its cross-pid twin.
        for e in ars:
            assert len(e["args"]["related_sync_op"]) == 2

    def test_p2p_amendment(self):
        per_process = {
            0: make_records(0, 0, [("send-forward", 30,
                                    {"group": [0, 1], "bytes": 1000})]),
            1: make_records(1, 0, [("recv-forward", 10,
                                    {"group": [0, 1], "bytes": 1000})]),
        }
        merged = aggregate_benchmark_data(per_process)
        events = transform_to_complete_events(merged)
        # send/recv have different names; give them the same logical name
        # for matching (the reference matches by expect-key; we align names)
        for e in events:
            if e["name"].startswith(("send", "recv")):
                e["name"] = "exchange-forward"
        related = build_dependencies(events)
        amend_p2p(events, related)
        ex = [e for e in events if e["name"] == "exchange-forward"]
        assert len(ex) == 2
        assert ex[0]["dur"] == ex[1]["dur"] == 10
        assert "orig_dur" in ex[0]["args"]


class TestDetector:
    def _records_with_slow_pid(self, slow_pid, n_pids=4, n_iters=8):
        """Slow chip: longer backward, shorter allreduce wait (it arrives
        last), equal elsewhere."""
        per_process = {}
        rng = np.random.default_rng(0)
        for pid in range(n_pids):
            recs = []
            for it in range(n_iters):
                slow = pid == slow_pid
                backward = 30.0 * (1.35 if slow else 1.0) + rng.normal(0, .1)
                allreduce = 10.0 * (0.5 if slow else 1.0) + rng.normal(0, .1)
                loss = 5.0 * (0.5 if slow else 1.0)
                phases = [
                    ("forward", 10.0, {}),
                    ("backward", backward, {}),
                    ("loss", loss, {}),
                    ("allreduce", allreduce,
                     {"group": list(range(n_pids))}),
                    ("all-reduce", allreduce,
                     {"group": list(range(n_pids))}),
                ]
                recs.extend(make_records(pid, it, phases))
            per_process[pid] = recs
        return per_process

    def test_detects_slow_process(self):
        per_process = self._records_with_slow_pid(slow_pid=2)
        merged = aggregate_benchmark_data(per_process)
        events = transform_to_complete_events(merged)
        related = build_dependencies(events)
        abnormal = try_detect(events, related)
        assert abnormal == [2], abnormal

    def test_no_false_positive_on_healthy_cluster(self):
        per_process = self._records_with_slow_pid(slow_pid=-1)  # none slow
        merged = aggregate_benchmark_data(per_process)
        events = transform_to_complete_events(merged)
        related = build_dependencies(events)
        abnormal = try_detect(events, related)
        assert abnormal == [], abnormal

    def test_precision_at_realistic_noise(self):
        """VERDICT weak #9: precision on multi-process traces WITH
        collectives, an injected ~20% slow chip, and 5% timing jitter —
        across seeds, the slow pid is always flagged and healthy pids
        never are."""
        for seed in range(5):
            rng = np.random.default_rng(seed)
            per_process = {}
            n_pids, slow_pid = 8, int(rng.integers(0, 8))
            for pid in range(n_pids):
                recs = []
                for it in range(12):
                    slow = pid == slow_pid

                    def jit(base):
                        return base * float(rng.normal(1.0, 0.05))

                    backward = jit(30.0 * (1.2 if slow else 1.0))
                    allreduce = jit(10.0 * (0.55 if slow else 1.0))
                    loss = jit(5.0 * (0.55 if slow else 1.0))
                    phases = [
                        ("forward", jit(10.0), {}),
                        ("backward", backward, {}),
                        ("loss", loss, {}),
                        ("allreduce", allreduce,
                         {"group": list(range(n_pids))}),
                        ("all-reduce", allreduce,
                         {"group": list(range(n_pids))}),
                    ]
                    recs.extend(make_records(pid, it, phases))
                per_process[pid] = recs
            merged = aggregate_benchmark_data(per_process)
            events = transform_to_complete_events(merged)
            related = build_dependencies(events)
            abnormal = try_detect(events, related)
            assert abnormal == [slow_pid], (seed, slow_pid, abnormal)

    def test_stage1_counts(self):
        per_process = self._records_with_slow_pid(slow_pid=1, n_iters=10)
        merged = aggregate_benchmark_data(per_process)
        events = transform_to_complete_events(merged)
        counts = detect_stage1(events)
        assert counts.get(1, 0) > 5
        assert all(c <= 5 for pid, c in counts.items() if pid != 1)


class TestTracedTraining:
    def test_e2e_trace_with_phases(self, devices8, tmp_path):
        """Traced training emits forward/backward/loss/allreduce/optimizer
        spans; aggregation produces a valid Chrome trace."""
        from megatronapp_tpu.config.parallel_config import ParallelConfig
        from megatronapp_tpu.config.training_config import (
            OptimizerConfig, TrainingConfig,
        )
        from megatronapp_tpu.config.transformer_config import (
            TransformerConfig,
        )
        from megatronapp_tpu.parallel.mesh import build_mesh
        from megatronapp_tpu.training.train import pretrain_gpt

        trace_dir = str(tmp_path / "trace")
        model = TransformerConfig(num_layers=2, hidden_size=64,
                                  num_attention_heads=4, vocab_size=128,
                                  max_position_embeddings=64)
        par = ParallelConfig(tensor_parallel=2)
        ctx = build_mesh(par, devices=devices8[:2])
        train = TrainingConfig(micro_batch_size=2, global_batch_size=8,
                               seq_length=32, train_iters=6, log_interval=3,
                               trace=True, trace_dir=trace_dir,
                               trace_interval=3,
                               continuous_trace_iterations=1)
        pretrain_gpt(model, par, train, OptimizerConfig(lr=1e-3), ctx=ctx)

        trace = aggregate_dir(trace_dir,
                              os.path.join(trace_dir, "agg.json"))
        names = {e["name"] for e in trace["traceEvents"]
                 if e.get("ph") == "X"}
        for expected in ("iteration", "train-step", "forward", "backward",
                         "loss", "allreduce", "optimizer"):
            assert expected in names, (expected, names)
        # microbatch fan-out: 2 microbatches → ≥2 forward spans per
        # traced iteration.
        fw = [e for e in trace["traceEvents"]
              if e.get("ph") == "X" and e["name"] == "forward"
              and e["args"]["iteration"] == 0]
        assert len(fw) >= 2
        assert os.path.exists(os.path.join(trace_dir, "agg.json"))


class TestTraceAnalytics:
    def test_report_from_real_trace(self, devices8, tmp_path):
        """Offline analytics (reference profiling/process_*.py parity) over
        a real traced training run: iteration stats, compute/comm ratio,
        phase windows."""
        from tests.test_training import learnable_batches

        from megatronapp_tpu.config.parallel_config import ParallelConfig
        from megatronapp_tpu.config.training_config import (
            OptimizerConfig, TrainingConfig,
        )
        from megatronapp_tpu.config.transformer_config import (
            TransformerConfig,
        )
        from megatronapp_tpu.parallel.mesh import build_mesh
        from megatronapp_tpu.trace.analytics import analyze
        from megatronapp_tpu.training.train import pretrain_gpt

        model = TransformerConfig(num_layers=2, hidden_size=64,
                                  num_attention_heads=4, vocab_size=128,
                                  max_position_embeddings=64)
        par = ParallelConfig()
        ctx = build_mesh(par, devices=devices8[:1])
        train = TrainingConfig(micro_batch_size=2, global_batch_size=4,
                               seq_length=32, train_iters=4,
                               log_interval=2, trace=True,
                               trace_interval=2,
                               continuous_trace_iterations=1,
                               trace_dir=str(tmp_path))
        pretrain_gpt(model, par, train, OptimizerConfig(lr=1e-3), ctx=ctx,
                     batch_iter=learnable_batches(32, 128, 4),
                     log_fn=lambda m: None)
        report = analyze(str(tmp_path))
        assert report["iteration_time"]["iterations"] >= 1
        assert report["iteration_time"]["mean_us"] > 0
        # The traced step carries phase spans on the CPU backend.
        assert report["phases"], report
        for pid, d in report["compute_comm"].items():
            assert 0.0 <= d["comm_fraction"] <= 1.0
