"""Input-pipeline autotuning (ISSUE 2 tentpole #1): the prefetch-depth
controller (deterministic synthetic producer/consumer waits — no clocks),
the adaptive queue it drives, the live PrefetchIterator wiring, the
streaming read coalescer, and the compile-budget alert + bucket-ladder
cap."""

import queue
import threading

import numpy as np
import pytest

from huggingface_sagemaker_tensorflow_distributed_tpu import obs
from huggingface_sagemaker_tensorflow_distributed_tpu.data.autotune import (
    ENV_AUTOTUNE,
    ENV_MAX,
    ENV_MEM_MB,
    PrefetchAutotuner,
)
from huggingface_sagemaker_tensorflow_distributed_tpu.data.pipeline import (
    ArrayDataset,
    PrefetchIterator,
    ShardedBatcher,
    _AdaptiveQueue,
)


# -- controller (pure: synthetic cumulative waits drive every decision) ------

class _FakePipeline:
    """Deterministic fake-clock producer/consumer: each consumed batch
    adds fixed per-batch waits to the cumulative stats — exactly the
    numbers ``_PrefetchStats`` would accumulate, without threads."""

    def __init__(self, tuner, consumer_wait_per_batch, producer_wait_per_batch,
                 batch_bytes=1000):
        self.tuner = tuner
        self.cw = consumer_wait_per_batch
        self.pw = producer_wait_per_batch
        self.batch_bytes = batch_bytes
        self.consumed = 0
        self.producer_wait = 0.0
        self.consumer_wait = 0.0
        self.decisions = []

    def run(self, batches):
        for _ in range(batches):
            self.consumed += 1
            # waits scale down once the queue is deep enough to cover
            # the burstiness: model the consumer wait as inversely
            # proportional to depth beyond the fixed floor
            self.consumer_wait += self.cw * (2.0 / max(self.tuner.depth, 1))
            self.producer_wait += self.pw
            d = self.tuner.observe(self.producer_wait, self.consumer_wait,
                                   self.consumed, self.batch_bytes)
            if d is not None:
                self.decisions.append(d)


def test_controller_grows_to_cap_on_input_bound():
    tuner = PrefetchAutotuner(min_depth=1, max_depth=16, window=4,
                              initial_depth=2)
    pipe = _FakePipeline(tuner, consumer_wait_per_batch=0.01,
                         producer_wait_per_batch=0.0)
    pipe.run(64)
    assert tuner.depth == 16                      # converged to the cap
    reasons = {r for _, r in pipe.decisions}
    assert reasons == {"input_bound"}
    # growth is monotone: 2 -> 4 -> 8 -> 16
    assert [d for d, _ in pipe.decisions] == [4, 8, 16]


def test_controller_saturates_on_steadily_slow_producer():
    """A producer that is simply slower than the consumer (constant
    consumer wait regardless of depth) must NOT ratchet to the cap:
    the first no-gain growth latches saturation."""
    tuner = PrefetchAutotuner(min_depth=1, max_depth=64, window=4,
                              initial_depth=2)
    consumed, cw = 0, 0.0
    for _ in range(100):
        consumed += 1
        cw += 0.003                  # depth-independent starvation
        tuner.observe(0.0, cw, consumed, 1000)
    assert tuner.depth == 4          # one speculative grow, then latched
    # regime change: producer catches up (consumer stops waiting), then
    # real burstiness resumes — growth is allowed again
    for _ in range(16):
        consumed += 1
        tuner.observe(0.0, cw, consumed, 1000)   # dc == 0: clears latch
    pipe = _FakePipeline(tuner, consumer_wait_per_batch=0.01,
                         producer_wait_per_batch=0.0)
    pipe.consumed = consumed
    pipe.consumer_wait = cw
    pipe.run(60)
    assert tuner.depth > 4


def test_controller_shrinks_with_hysteresis_when_compute_bound():
    tuner = PrefetchAutotuner(min_depth=1, max_depth=16, window=4,
                              initial_depth=8, shrink_patience=3)
    pipe = _FakePipeline(tuner, consumer_wait_per_batch=0.0,
                         producer_wait_per_batch=0.01)
    # fewer than patience windows: no shrink yet (hysteresis)
    pipe.run(8)
    assert tuner.depth == 8 and not pipe.decisions
    pipe.run(120)
    assert tuner.depth == 1                       # decayed to the floor
    assert all(r == "compute_bound" for _, r in pipe.decisions)
    # one step per decision, never more (slow shrink)
    depths = [d for d, _ in pipe.decisions]
    assert depths == sorted(depths, reverse=True)
    assert all(a - b == 1 for a, b in zip(depths, depths[1:]))


def test_controller_memory_cap_bounds_depth():
    tuner = PrefetchAutotuner(min_depth=1, max_depth=64, window=2,
                              initial_depth=2,
                              mem_budget_bytes=10 * 1000)
    pipe = _FakePipeline(tuner, consumer_wait_per_batch=0.01,
                         producer_wait_per_batch=0.0, batch_bytes=1000)
    pipe.run(64)
    assert tuner.depth == 10                      # 10kB budget / 1kB batch
    assert tuner.hard_cap() == 10
    # a bigger batch shape arrives (bucket ladder): immediate clamp
    d = tuner.observe(pipe.producer_wait, pipe.consumer_wait,
                      pipe.consumed + 1, batch_bytes=2000)
    assert d == (5, "mem_cap")


def test_controller_noise_floor_holds_depth():
    tuner = PrefetchAutotuner(window=2, initial_depth=4)
    # microscopic waits on both sides: neither grow nor shrink
    for i in range(1, 41):
        assert tuner.observe(i * 1e-6, i * 1e-6, i) is None
    assert tuner.depth == 4


def test_from_env(monkeypatch):
    monkeypatch.setenv(ENV_AUTOTUNE, "0")
    assert PrefetchAutotuner.from_env() is None
    monkeypatch.setenv(ENV_AUTOTUNE, "1")
    monkeypatch.setenv(ENV_MAX, "7")
    monkeypatch.setenv(ENV_MEM_MB, "1")
    tuner = PrefetchAutotuner.from_env()
    assert tuner.max_depth == 7
    assert tuner.mem_budget_bytes == 1 << 20


# -- adaptive queue ----------------------------------------------------------

def test_adaptive_queue_capacity_change_unblocks_producer():
    q = _AdaptiveQueue(1)
    q.put("a")
    with pytest.raises(queue.Full):
        q.put("b", timeout=0.05)
    unblocked = threading.Event()

    def producer():
        q.put("b", timeout=5)
        unblocked.set()

    t = threading.Thread(target=producer, daemon=True)
    t.start()
    q.set_capacity(2)                 # wakes the blocked producer
    assert unblocked.wait(timeout=5)
    assert q.get() == "a" and q.get() == "b"
    with pytest.raises(queue.Empty):
        q.get_nowait()


def test_prefetch_iterator_autotuned_end_to_end():
    """Live threads: an autotuned iterator delivers every item in order
    and the achieved depth stays within [min, hard_cap]."""
    tuner = PrefetchAutotuner(min_depth=1, max_depth=8, window=2)
    it = PrefetchIterator(iter([{"x": np.zeros(4)} for _ in range(50)]),
                          autotuner=tuner)
    got = [item for item in it]
    assert len(got) == 50
    assert 1 <= it.depth <= tuner.hard_cap()


def test_batcher_carries_converged_depth_across_epochs():
    """A new epoch's controller starts from the previous epoch's
    converged depth, not back at 2."""
    from huggingface_sagemaker_tensorflow_distributed_tpu.parallel import (
        MeshConfig,
        build_mesh,
    )

    mesh = build_mesh(MeshConfig())
    ds = ArrayDataset({
        "input_ids": np.zeros((32, 8), np.int32),
        "attention_mask": np.ones((32, 8), np.int32),
        "labels": np.zeros(32, np.int32),
    })
    b = ShardedBatcher(ds, 8, mesh, shuffle=False,
                       process_index=0, process_count=1)
    it0 = b.global_arrays(0)
    assert b._auto_tuner is not None
    it0.close()
    b._auto_tuner.depth = 8          # pretend epoch 0 converged here
    it1 = b.global_arrays(1)
    assert b._auto_tuner.depth == 8  # fresh controller, seeded depth
    assert it1.depth == 8
    it1.close()


# -- streaming read coalescer ------------------------------------------------

def test_line_corpus_coalesced_reads_adapt_and_stay_exact(tmp_path):
    """Near-adjacent rows read in one call; sparse access shrinks the
    gap (waste-driven), dense access grows it back — and the decoded
    rows are byte-identical either way."""
    from huggingface_sagemaker_tensorflow_distributed_tpu.data.streaming import (
        LineCorpus,
    )

    path = tmp_path / "c.txt"
    lines = [f"row {i} " + "x" * (i % 97) for i in range(400)]
    path.write_text("\n".join(lines) + "\n")
    corpus = LineCorpus(str(path))
    # dense (adjacent) window: big gap is all signal — it grows
    g0 = corpus._coalesce_gap
    dense = np.arange(64)
    assert corpus._read_lines(dense) == [lines[i] for i in dense]
    assert corpus._coalesce_gap >= g0
    # sparse far-apart rows: coalescing wastes most bytes — gap shrinks
    sparse = np.arange(0, 400, 97)
    for _ in range(6):
        assert corpus._read_lines(sparse) == [lines[i] for i in sparse]
    assert corpus._coalesce_gap < g0
    # duplicates and reverse order still come back in idx order
    tricky = np.asarray([5, 5, 300, 2])
    assert corpus._read_lines(tricky) == [lines[5], lines[5],
                                          lines[300], lines[2]]


# -- compile budget (ROADMAP "Compile-time budget") --------------------------

@pytest.fixture()
def obs_dir(tmp_path):
    out = tmp_path / "telemetry"
    obs.reset(out_dir=str(out), enabled=True)
    yield out
    obs.reset()


def _events(out):
    path = out / "events.jsonl"
    if not path.exists():
        return []
    return [e for _, e, err in obs.iter_events(str(path)) if err is None]


def test_compile_budget_alert_and_latch(obs_dir, capsys):
    tracker = obs.compile_tracker()
    tracker.budget_s = 0.5
    assert not obs.compile_budget_exceeded()
    tracker.observe(tracker.BACKEND_COMPILE, 0.3)
    assert not obs.compile_budget_exceeded()
    tracker.observe(tracker.BACKEND_COMPILE, 0.4)   # crosses 0.5s
    assert obs.compile_budget_exceeded()
    tracker.observe(tracker.BACKEND_COMPILE, 0.4)   # alert fires ONCE
    alerts = [e for e in _events(obs_dir) if e["type"] == "alert"]
    assert len(alerts) == 1
    assert alerts[0]["name"] == "compile_budget"
    assert "HSTD_COMPILE_BUDGET_S" in alerts[0]["message"]
    assert "COMPILE BUDGET" in capsys.readouterr().err
    # the events file validates against the schema with the new types
    count, errors = obs.validate_events_file(str(obs_dir / "events.jsonl"))
    assert not errors and count >= 4


def test_bucket_ladder_capped_when_over_budget(obs_dir):
    """Once the budget latches, the batcher stops minting NEW bucket
    widths: unseen rungs widen to an already-used width (or the full
    column width), so no further compiles happen."""
    from huggingface_sagemaker_tensorflow_distributed_tpu.parallel import (
        MeshConfig,
        build_mesh,
    )

    n, width = 16, 64
    ids = np.zeros((n, width), np.int32)
    mask = np.zeros((n, width), np.int32)
    # batch 0 rows: length 10 (bucket 16); batch 1 rows: length 40
    # (bucket 48 — a NEW width once the budget is blown)
    for i in range(n):
        L = 10 if i < 8 else 40
        ids[i, :L] = 7
        mask[i, :L] = 1
    ds = ArrayDataset({"input_ids": ids, "attention_mask": mask,
                       "labels": np.zeros(n, np.int32)})
    mesh = build_mesh(MeshConfig())

    def widths():
        b = ShardedBatcher(ds, 8, mesh, shuffle=False,
                           bucket_sizes=[16, 32, 48, 64],
                           process_index=0, process_count=1)
        return [batch["input_ids"].shape[1] for batch in b.local_batches(0)]

    assert widths() == [16, 48]                   # unconstrained ladder
    tracker = obs.compile_tracker()
    tracker.budget_s = 0.1
    tracker.observe(tracker.BACKEND_COMPILE, 1.0)  # blow the budget
    # a FRESH batcher (no used widths yet) must fall back to full width
    # for both batches instead of minting 16 then 48
    assert widths() == [64, 64]


def test_bucket_ladder_multihost_caps_only_on_agreement(obs_dir):
    """Multi-host ladder capping (ROADMAP leftover from PR 2): a
    process_count > 1 batcher must IGNORE the host-local budget latch —
    the budget crosses at a host-local instant, and bucket widths
    derive from shared state, so one host capping alone would ship
    mismatched shapes into collectives. It caps only once the trainer's
    epoch-boundary collective (``agree_compile_budget_crossed``) has
    latched the agreed flag on every host together."""
    from huggingface_sagemaker_tensorflow_distributed_tpu.parallel import (
        MeshConfig,
        build_mesh,
    )
    from huggingface_sagemaker_tensorflow_distributed_tpu.parallel.distributed import (
        agree_compile_budget_crossed,
    )

    n, width = 16, 64
    ids = np.zeros((n, width), np.int32)
    mask = np.zeros((n, width), np.int32)
    for i in range(n):
        L = 10 if i < 8 else 40
        ids[i, :L] = 7
        mask[i, :L] = 1
    ds = ArrayDataset({"input_ids": ids, "attention_mask": mask,
                       "labels": np.zeros(n, np.int32)})
    mesh = build_mesh(MeshConfig())

    def widths():
        b = ShardedBatcher(ds, 8, mesh, shuffle=False,
                           bucket_sizes=[16, 32, 48, 64],
                           process_index=0, process_count=2)
        return [batch["input_ids"].shape[1] for batch in b.local_batches(0)]

    tracker = obs.compile_tracker()
    tracker.budget_s = 0.1
    tracker.observe(tracker.BACKEND_COMPILE, 1.0)   # local crossing only
    assert obs.compile_budget_exceeded()
    assert not obs.compile_budget_capped(2)
    assert widths() == [16, 48]                    # still minting
    # the epoch-boundary agreement (single-process: trivially local)
    assert agree_compile_budget_crossed(obs.compile_budget_exceeded())
    obs.set_compile_budget_agreed()
    assert obs.compile_budget_capped(2)
    assert widths() == [64, 64]                    # capped, all hosts alike
