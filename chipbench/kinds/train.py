"""A training cell: the program's ``Trainer.fit`` over a seeded
synthetic corpus through ``ShardedBatcher``, timed by the benchmark.

The construction (model -> ``Trainer`` -> ``ShardedBatcher`` ->
``Trainer.fit``) is a copy of ``bench.py::build_harness``; the window,
the counting and the check of correctness are the benchmark's own.
"""

from __future__ import annotations

import importlib
import math
import tempfile
import time

import numpy as np

from chipbench import arith, device


class _Clocked:
    """A batcher that serves the underlying ``ShardedBatcher``'s epochs
    one after another until a deadline or a step count, so that ONE
    ``Trainer.fit`` call lasts the whole window. It is also where the
    benchmark gets control between steps (profiler start and stop)."""

    def __init__(self, batcher, *, steps=None, seconds=None, session=None):
        self.inner = batcher
        self.global_batch_size = batcher.global_batch_size
        self.token_log = getattr(batcher, "token_log", None)
        self.bucket_sizes = getattr(batcher, "bucket_sizes", None)
        self.steps, self.seconds, self.session = steps, seconds, session
        self.served = 0
        self.t_open = None

    def steps_per_epoch(self) -> int:
        return self.steps if self.steps is not None else 1 << 40

    def global_arrays(self, epoch: int = 0, start_step: int = 0):
        self.t_open = time.perf_counter()
        inner_epoch = 0
        while True:
            it = self.inner.global_arrays(inner_epoch, 0)
            try:
                while True:
                    now = time.perf_counter() - self.t_open
                    if self.session is not None:
                        self.session.tick(now)
                    if self.seconds is not None and now >= self.seconds:
                        return
                    if self.steps is not None and self.served >= self.steps:
                        return
                    with device.annotate("next_batch"):
                        batch = next(it, None)
                    if batch is None:
                        break
                    self.served += 1
                    yield batch
            finally:
                if hasattr(it, "close"):
                    it.close()
            inner_epoch += 1


def _corpus(traffic: dict, seed: int, n: int):
    """Two-class synthetic text, every sample drawn to the traffic's
    length distribution (in words; the word-hash tokenizer makes one
    token a word, and the reference pads or cuts to ``seq_len``)."""
    import random

    from huggingface_sagemaker_tensorflow_distributed_tpu.data.sources import (
        synthetic_text_classification,
    )

    from chipbench.loadgen import draw_length

    rng = random.Random(seed)
    texts, labels = [], []
    for i in range(n):
        words = draw_length(traffic["sample_words"], rng)
        # a pair (label 0, label 1) of that length; keep the one whose
        # label-correlated words match this sample's label
        t, l = synthetic_text_classification(
            2, seed=rng.randrange(1 << 30), min_len=words, max_len=words)
        texts.append(t[i % 2])
        labels.append(l[i % 2])
    return texts, labels


def run(cell, seed: int, seconds: float, trace: bool, devs: list,
        compiles, t_start: float, keep_dir=None) -> dict:
    import jax

    from huggingface_sagemaker_tensorflow_distributed_tpu import obs
    from huggingface_sagemaker_tensorflow_distributed_tpu.config import (
        TrainConfig,
    )
    from huggingface_sagemaker_tensorflow_distributed_tpu.data import (
        ArrayDataset,
        ShardedBatcher,
        WordHashTokenizer,
    )
    from huggingface_sagemaker_tensorflow_distributed_tpu.parallel import (
        MeshConfig,
        build_mesh,
    )
    from huggingface_sagemaker_tensorflow_distributed_tpu.train import Trainer

    cfg, traffic = cell.config, cell.traffic
    dep = cfg["deployment"]
    platform = devs[0].platform
    chips = len(devs)
    per_chip = int(traffic["per_chip_batch"])
    seq_len = int(traffic["seq_len"])
    global_batch = per_chip * chips

    mark = device.SetupMarks(t_start)

    family = importlib.import_module("chipbench.families." + cfg["family"])
    # TrainConfig.seed stays the program's default: the trainer bakes its
    # dropout key into the compiled step, so a seed of the run's own
    # would miss the compile cache in every run (found on the chip,
    # PR 22). --seed makes the weights and the corpus.
    tcfg = TrainConfig(dtype=dep["dtype"], train_batch_size=per_chip,
                       max_seq_length=seq_len, log_every_steps=0)
    model, params = family.build(
        cfg, seed, attention_impl=tcfg.resolve_attention_impl(platform),
        dtype=dep["dtype"])
    mesh = build_mesh(MeshConfig(**traffic.get("mesh", {"dp": -1})),
                      devices=devs)
    jax.block_until_ready(params)
    mark("params")
    trainer = Trainer(tcfg, model, params, mesh)
    del params
    jax.block_until_ready(trainer.state.params)
    mark("trainer")

    texts, labels = _corpus(traffic, seed, global_batch * int(
        traffic["corpus_batches"]))
    ds = ArrayDataset.from_texts(WordHashTokenizer(), texts, labels,
                                 max_length=seq_len)
    batcher = ShardedBatcher(ds, global_batch, mesh, shuffle=True, seed=seed)
    mark("corpus")

    # warm-up: the one shape this cell uses; the first step compiles
    trainer.fit(_Clocked(batcher, steps=int(traffic["warmup_steps"])),
                epochs=1)
    jax.block_until_ready(trainer.state.params)
    mark("warmup")

    tel_dir = None
    session = None
    if trace:
        # telemetry on AFTER the trainer is built: the compiled step is
        # the one an untraced run executes
        tel_dir = tempfile.mkdtemp(prefix="chipbench_obs_")
        obs.configure(out_dir=tel_dir, enabled=True)
        # mid-window; the host runs some thirty steps ahead of the
        # device, so the stall of stopping the profiler idles nothing
        session = device.TraceSession(0.5 * seconds,
                                      traffic["trace_seconds"], keep_dir)

    clocked = _Clocked(batcher, seconds=seconds, session=session)
    setup_s = time.perf_counter() - t_start
    compiles.open_window()
    t0 = time.perf_counter()
    with device.annotate("trainer.fit"):
        history = trainer.fit(clocked, epochs=1)
        jax.block_until_ready(trainer.state.params)
    window_s = time.perf_counter() - t0
    compiles.close_window()
    if session is not None:
        session.stop()
        obs.shutdown()

    samples = clocked.served * global_batch
    rate = samples / window_s / chips
    loss_finite = all(math.isfinite(x) for x in history["loss"])

    # correctness, outside the window
    check = _check(cell, trainer, model, ds, chips, seed)
    correct = (loss_finite and check["ok"] and compiles.in_window == 0)

    counters = {
        "samples": samples, "steps": clocked.served,
        "global_batch": global_batch, "per_chip_batch": per_chip,
        "seq_len": seq_len, "loss_last": history["loss"][-1],
        "flops_per_sample": arith.encoder_train_flops_per_sample(
            cfg["hidden_size"], cfg["intermediate_size"],
            cfg["num_hidden_layers"], seq_len),
        "heads": cfg["num_attention_heads"],
        "head_dim": cfg["hidden_size"] // cfg["num_attention_heads"],
        "layers": cfg["num_hidden_layers"],
        "check": check,
        "setup_marks_s": mark.as_dict(),
    }
    return {
        "correct": bool(correct), "attempted": samples, "failed": 0,
        "values": {"train_samples_per_s_per_chip": rate, "setup_s": setup_s},
        "window_s": window_s, "counters": counters,
        "events": device.read_events(tel_dir) if tel_dir else [],
        "session": session,
    }


def _check(cell, trainer, model, ds, chips: int, seed: int) -> dict:
    """The system's forward (compute type and attention kernel as
    trained) against the plain float32 reference on a seeded batch of 4
    sequences at the cell's widths, with the trained parameters; and on
    several chips, that the replicas still agree."""
    import jax
    import jax.numpy as jnp

    cfg = cell.config
    ref = importlib.import_module("chipbench.reference." + cfg["family"])
    rng = np.random.default_rng(seed)
    rows = rng.choice(len(ds), size=4, replace=False)
    cols = {k: jnp.asarray(np.asarray(v)[rows]) for k, v in ds.columns.items()}
    tt = cols.get("token_type_ids", jnp.zeros_like(cols["input_ids"]))
    params = jax.device_get(trainer.state.params)
    params = jax.device_put(params, jax.devices()[0])
    got = jax.jit(lambda p: model.apply(
        {"params": p}, cols["input_ids"], cols["attention_mask"], tt,
        deterministic=True))(params).astype(jnp.float32)
    want = jax.jit(lambda p: ref.forward(
        p, cfg, cols["input_ids"], cols["attention_mask"], tt))(params)
    err = float(jnp.max(jnp.abs(got - want)))
    scale = float(jnp.max(jnp.abs(want)))
    tol = float(cfg["deployment"]["logit_tolerance"])
    out = {"max_abs_err": err, "ref_max_abs": scale, "tolerance": tol,
           "ok": bool(np.isfinite(err) and err <= tol)}
    if chips > 1:
        out["replica_divergence"] = float(trainer.check_replica_divergence())
        out["ok"] = out["ok"] and out["replica_divergence"] == 0.0
    return out
