"""A serving cell: the program's ``ServeEngine`` driven by the
benchmark's own single-threaded load driver.

Open loop: arrivals are scheduled over the whole window from the seed,
each request is timed from when it was DUE, the generator's lateness is
reported, and after the window the run drains for a stated grace; a
request unfinished by then, or refused, is ``failed``. Closed loop: a
fixed number of clients, each sending its next request when the last
one finished. Token times are polled by the driver after each
``step()``: the engine is left as a user runs it.

A closed loop with ``loop.laps`` runs its window as LAPS. A lap is
``laps.steps`` engine iterations of the loop from an empty engine, on an
engine built for it, over the same request lengths every time (the
tokens are new); the window holds as many whole laps as fit, and what is
left of it is a last lap that is cut. A whole lap commits the same
tokens every time, so its seconds are one reading of the same work, and
tokens per second is the MEDIAN lap's: one stall of the host or of the
device spoils one lap and not the run.
"""

from __future__ import annotations

import gc
import importlib
import tempfile
import time
from typing import NamedTuple

import numpy as np

from chipbench import device, stats
from chipbench.loadgen import make_requests


class _Track:
    __slots__ = ("req", "due_t", "sent_t", "seen", "first_t", "last_t",
                 "done_t")

    def __init__(self, due_t):
        self.due_t = due_t
        self.req = None
        self.sent_t = self.first_t = self.last_t = self.done_t = None
        self.seen = 0


def _generated(req) -> int:
    # tokens folded into the prompt by a recompute preemption count too
    return (len(req.prompt) - req.orig_prompt_len) + len(req.output)


class Lap(NamedTuple):
    steps: int         # engine iterations it ran
    tokens: int        # output tokens committed inside it
    seconds: float     # first submission to the poll after the last step
    whole: bool        # ran all its steps before the window closed


def lap_rate(laps: list):
    """Tokens per second of the median WHOLE lap; None without one."""
    return stats.median([lap.tokens / lap.seconds for lap in laps
                         if lap.whole and lap.seconds > 0])


def _laps_summary(laps: list) -> dict:
    whole = [lap for lap in laps if lap.whole]
    secs = [lap.seconds for lap in whole]
    return {"whole": len(whole), "cut": len(laps) - len(whole),
            "steps": sorted({lap.steps for lap in whole}),
            "tokens": sorted({lap.tokens for lap in whole}),
            "seconds_min_median_max": [min(secs), stats.median(secs),
                                       max(secs)] if secs else None,
            "seconds_first_8": secs[:8]}


class Driver:
    """Submits, steps and polls, in one thread."""

    def __init__(self, loop: dict, seconds: float, session=None,
                 sample_kv: bool = False):
        self.loop, self.seconds, self.session = loop, seconds, session
        self.sample_kv = sample_kv
        self.engine = None
        self.plans: list = []
        self.tracks: list = []
        self.live: list = []
        self.gaps: list = []          # (stamp offset, gap seconds)
        self.token_stamps: list = []  # stamp offsets of output tokens
        self.refused = 0
        self.kv_live_tokens: list = []
        self.kv_held_blocks = 0       # most blocks that held KV at once
        self.backlog: list = []       # (offset, requests in the system)
        self.steps = 0
        self.laps: list = []
        self.outputs: list = []       # (prompt, output ids) of the finished
        self.preemptions = 0
        self.t0 = None                # when the window opened
        self._next = 0
        self._harvested = 0
        self._polled_t = None

    def _submit(self, t0: float, due_off) -> None:
        plan = self.plans[self._next]
        self._next += 1
        now = time.perf_counter()
        tr = _Track(now if due_off is None else t0 + due_off)
        tr.sent_t = now
        self.tracks.append(tr)
        try:
            tr.req = self.engine.submit(
                plan.prompt, plan.max_new_tokens,
                arrival_s=None if due_off is None else tr.due_t)
        except ValueError:
            self.refused += 1
            return
        self.live.append(tr)

    def _poll(self, t0: float) -> int:
        now = self._polled_t = time.perf_counter()
        finished = 0
        keep = []
        for tr in self.live:
            n = _generated(tr.req)
            while tr.seen < n:
                tr.seen += 1
                self.token_stamps.append(now - t0)
                if tr.first_t is None:
                    tr.first_t = now
                else:
                    self.gaps.append((now - t0, now - tr.last_t))
                tr.last_t = now
            if tr.req.finish_t is not None:
                tr.done_t = now
                finished += 1
            else:
                keep.append(tr)
        self.live = keep
        if self.sample_kv:
            blocks = self.engine.blocks
            # held by a request, or parked in the prefix cache's LRU
            self.kv_held_blocks = max(self.kv_held_blocks,
                                      blocks.num_used + blocks.num_cached)
            slots = self.engine.sched.decode_slots()
            if slots:
                self.kv_live_tokens.append(
                    sum(s.context_len for s in slots))
        return finished

    def serve(self, engine, plans: list, max_steps=None) -> Lap:
        """Serve ``plans`` on ``engine`` until the window closes or,
        with ``max_steps``, that many iterations have run. The window
        opens with the first call."""
        if self.t0 is None:
            self.t0 = time.perf_counter()
        self.engine, self.plans, self.live, self._next = engine, plans, [], 0
        eng, closed, t0 = engine, self.loop["kind"] == "closed", self.t0
        tokens_before = len(self.token_stamps)
        t_lap = self._polled_t = time.perf_counter()
        steps = 0
        if closed:
            for _ in range(min(int(self.loop["clients"]), len(plans))):
                self._submit(t0, None)
        while max_steps is None or steps < max_steps:
            now = time.perf_counter() - t0
            if now >= self.seconds:
                break
            if self.session is not None:
                self.session.tick(now)
            if not closed:
                with device.annotate("submit"):
                    while (self._next < len(plans)
                           and plans[self._next].due_s <= now):
                        self._submit(t0, plans[self._next].due_s)
            if eng.has_work():
                with device.annotate("engine.step"):
                    eng.step()
                steps += 1
                with device.annotate("poll"):
                    done = self._poll(t0)
                self.backlog.append((now, len(self.live)))
                if closed:
                    with device.annotate("submit"):
                        for _ in range(done):
                            if self._next < len(plans):
                                self._submit(t0, None)
            else:
                with device.annotate("wait_arrival"):
                    nxt = (plans[self._next].due_s
                           if self._next < len(plans) else self.seconds)
                    time.sleep(max(0.0, min(nxt - now, 0.002)))
        self.steps += steps
        lap = Lap(steps, len(self.token_stamps) - tokens_before,
                  self._polled_t - t_lap, steps == max_steps)
        self.laps.append(lap)
        return lap

    def harvest(self) -> None:
        """Keep what the check and the counters need of the engine's
        finished requests; the engine may go after this."""
        for tr in self.tracks[self._harvested:]:
            if tr.done_t is not None:
                self.outputs.append(
                    (tr.req.prompt[:tr.req.orig_prompt_len],
                     self.engine.output_ids(tr.req)))
        self._harvested = len(self.tracks)
        self.preemptions += int(self.engine.sched.n_preemptions)

    def run(self, engine, plans: list) -> float:
        """The measured window on one engine; returns its true length
        in seconds."""
        self.serve(engine, plans)
        return time.perf_counter() - self.t0

    def run_laps(self, make_engine, make_plans, steps: int,
                 released=None) -> float:
        """The measured window as laps of ``steps`` iterations, each on
        an engine of its own from ``make_engine()`` over the requests
        ``make_plans(lap)``. ``released()`` is called once a lap's
        engine has been let go, before the next is built. Returns the
        window's true length in seconds."""
        import jax

        self.t0 = time.perf_counter()
        while time.perf_counter() - self.t0 < self.seconds:
            with device.annotate("lap_setup"):
                engine, plans = make_engine(), make_plans(len(self.laps))
            self.serve(engine, plans, steps)
            with device.annotate("lap_teardown"):
                # nothing of this engine is still running when it goes
                jax.block_until_ready(jax.live_arrays())
                self.harvest()
                del engine
                self.engine, self.live = None, []
                gc.collect()
                if released is not None:
                    released()
        return time.perf_counter() - self.t0

    def drain(self, grace_s: float) -> None:
        """Open loop: send what was due and still held, then step until
        every request has finished or the grace has passed."""
        t_end = time.perf_counter() + grace_s
        while (self._next < len(self.plans)
               and self.plans[self._next].due_s < self.seconds):
            self._submit(self.t0, self.plans[self._next].due_s)
        while self.live and self.engine.has_work() \
                and time.perf_counter() < t_end:
            self.engine.step()
            self._poll(self.t0)


def build_engine(model, params, dep: dict):
    """The program's engine at the configuration's deployment; every
    option the deployment does not name stays at the program's default."""
    from huggingface_sagemaker_tensorflow_distributed_tpu.serve.engine import (
        ServeEngine,
    )

    return ServeEngine(
        model, params, num_slots=int(dep["num_slots"]),
        block_size=int(dep["block_size"]),
        prefill_chunk=int(dep["prefill_chunk"]),
        max_model_len=int(dep["max_model_len"]),
        kv_pool_bytes=int(dep["kv_pool_bytes"]))


def live_bytes() -> int:
    """Bytes of every array JAX holds on a device right now."""
    import jax

    return sum(int(a.nbytes) for a in jax.live_arrays())


def check_released(held_before: int, pool_bytes: int) -> None:
    """An engine that was let go must be gone before the next is built:
    two pools do not fit the chip beside the weights. Stops the run if
    the device holds half a pool more than before the first engine."""
    held = live_bytes() - held_before
    if held > pool_bytes // 2:
        raise SystemExit(f"chipbench: {held} B of an engine that was let "
                         "go are still held on the device")


def run(cell, seed: int, seconds: float, trace: bool, devs: list,
        compiles, t_start: float, keep_dir=None) -> dict:
    import jax

    from huggingface_sagemaker_tensorflow_distributed_tpu import obs

    cfg, traffic = cell.config, cell.traffic
    dep, loop = cfg["deployment"], traffic["loop"]
    mark = device.SetupMarks(t_start)

    family = importlib.import_module("chipbench.families." + cfg["family"])
    model, params = family.build(cfg, seed, dtype=dep["dtype"])
    jax.block_until_ready(params)
    mark("params")
    param_bytes = sum(x.size * x.dtype.itemsize
                      for x in jax.tree_util.tree_leaves(params))
    held_before = live_bytes()
    engine = build_engine(model, params, dep)
    mark("engine")
    engine.warmup()
    mark("warmup")
    sizes = {"num_slots": engine.num_slots,
             "token_bytes": int(engine.blocks.token_bytes),
             "num_blocks": int(engine.blocks.num_blocks),
             "block_bytes": int(engine.blocks.block_bytes),
             "gather_buckets": list(engine.gather_buckets)}
    plans = make_requests(traffic, seed, cfg["vocab_size"], seconds)
    too_long = [p for p in plans if len(p.prompt) + p.max_new_tokens
                > engine.max_model_len]
    if too_long:
        raise SystemExit(f"chipbench: {len(too_long)} request(s) exceed "
                         f"max_model_len {engine.max_model_len}")
    laps = loop.get("laps") if loop["kind"] == "closed" else None

    def released():
        check_released(held_before,
                       sizes["num_blocks"] * sizes["block_bytes"])

    dry_compiles = None
    if laps:
        # every lap gets an engine of its own, built inside the window
        # (its programs are the process's, compiled above); the one
        # that warmed them up has done its work
        jax.block_until_ready(jax.live_arrays())
        del engine
        gc.collect()
        released()
        # a lap's opening, once, on an engine built as a lap's is: what
        # the first iterations of a new engine compile or load beyond
        # warmup() (new pools, the first token feeds), they do here
        before = compiles.total
        dry = Driver(loop, float("inf"))
        dry.serve(build_engine(model, params, dep), plans,
                  int(laps.get("dry_steps", 4)))
        jax.block_until_ready(jax.live_arrays())
        del dry
        gc.collect()
        released()
        dry_compiles = compiles.total - before
        mark("dry_lap")

    tel_dir = session = None
    if trace:
        tel_dir = tempfile.mkdtemp(prefix="chipbench_obs_")
        obs.configure(out_dir=tel_dir, enabled=True)
        obs.compile_tracker()
        # a few seconds of the window: its last, stopped once it has
        # closed, or those from `trace_start_s`, stopped when they end
        length = min(seconds, traffic["trace_seconds"])
        start = traffic.get("trace_start_s")
        if start is None or start + length >= seconds:
            session = device.TraceSession(seconds - length, out_dir=keep_dir)
        else:
            session = device.TraceSession(start, length, out_dir=keep_dir)
    drv = Driver(loop, seconds, session, sample_kv=trace)

    gc.collect()
    gc.freeze()     # the model and the plans are not garbage: keep the
    #                 collector's full passes out of the tails
    setup_s = time.perf_counter() - t_start
    compiles.open_window()
    if laps:
        window_s = drv.run_laps(
            lambda: build_engine(model, params, dep),
            lambda lap: plans if lap == 0 else make_requests(
                traffic, seed, cfg["vocab_size"], seconds, lap=lap),
            int(laps["steps"]), released)
    else:
        window_s = drv.run(engine, plans)
    compiles.close_window()
    if session is not None:
        session.stop()
    if loop["kind"] == "open":
        drv.drain(float(loop["drain_grace_s"]))
    if not laps:
        drv.harvest()
        del engine
        drv.engine = None
    program_compiles = None
    if trace:
        tracker = obs.compile_tracker()
        program_compiles = None if tracker is None else tracker.count
        obs.shutdown()

    # -- what the window held ------------------------------------------------
    closed = loop["kind"] == "closed"
    if closed:
        counted = [t for t in drv.tracks if t.done_t is not None
                   and t.done_t - drv.t0 <= window_s]
        failed = drv.refused
        attempted = len(counted) + failed
    else:
        counted = drv.tracks
        failed = sum(1 for t in drv.tracks if t.done_t is None)
        attempted = len(drv.tracks)
    tokens_in = sum(1 for s in drv.token_stamps if s <= window_s)
    gaps_in = [g for s, g in drv.gaps if s <= window_s]
    grace_end = time.perf_counter()
    ttft = [((t.first_t if t.first_t is not None else grace_end) - t.due_t)
            for t in counted]
    # laps: the median whole lap's rate; a window that held no whole lap
    # gives the rate over all of it, and the run is not `correct`
    rate = lap_rate(drv.laps) if laps else tokens_in / window_s
    laps_ok = not laps or rate is not None
    values = {"setup_s": setup_s,
              "serve_out_tok_per_s": (rate if rate is not None
                                      else tokens_in / window_s),
              "itl_p99_ms": 1e3 * (stats.percentile(gaps_in, 99.0) or 0.0),
              "ttft_p90_ms": 1e3 * (stats.percentile(ttft, 90.0) or 0.0)}

    reqs = [t.req for t in drv.tracks if t.req is not None]
    started = [r for r in reqs if r.prefix_prompt_tokens]
    counters = {
        "requests_sent": len(drv.tracks), "requests_finished": sum(
            1 for t in drv.tracks if t.done_t is not None),
        "tokens_in_window": tokens_in, "gaps_in_window": len(gaps_in),
        "steps": drv.steps, "refused": drv.refused,
        "laps": _laps_summary(drv.laps) if laps else None,
        "lateness": stats.lateness(
            [t.due_t for t in drv.tracks], [t.sent_t for t in drv.tracks]),
        "ttft_p50_ms": 1e3 * (stats.median(ttft) or 0.0),
        "itl_p50_ms": 1e3 * (stats.median(gaps_in) or 0.0),
        "prompt_tokens": sum(r.prefix_prompt_tokens for r in started),
        "prefix_cached_tokens": sum(r.prefix_cached_tokens for r in started),
        "param_bytes": int(param_bytes), **sizes,
        "kv_held_blocks_peak": drv.kv_held_blocks if trace else None,
        "preemptions": drv.preemptions,
        "kv_live_tokens_mean": (float(np.mean(drv.kv_live_tokens))
                                if drv.kv_live_tokens else None),
        "program_compiles_total": program_compiles,
        "benchmark_compiles_total": compiles.total,
        "dry_lap_compiles": dry_compiles,
        "setup_marks_s": mark.as_dict(),
    }

    # -- correctness, outside the window ------------------------------------
    outputs = drv.outputs
    del drv
    gc.unfreeze()
    gc.collect()
    check = _check(cell, params, outputs, seed)
    counters["check"] = check
    correct = (check["ok"] and compiles.in_window == 0 and failed == 0
               and laps_ok)
    return {"correct": bool(correct), "attempted": attempted,
            "failed": failed, "values": values, "window_s": window_s,
            "counters": counters,
            "events": device.read_events(tel_dir) if tel_dir else [],
            "session": session}


def _check(cell, params, outputs: list, seed: int) -> dict:
    """For a seeded sample of finished requests, the reference's
    teacher-forced logits over prompt + engine output must put every
    token the engine chose within ``token_margin`` of the reference's
    maximum at that position. With random weights an argmax can flip on
    rounding; a wrong cache or position cannot pass: the reference's
    logit of an unrelated token lies several units under its maximum."""
    import jax.numpy as jnp

    cfg = cell.config
    ref = importlib.import_module("chipbench.reference." + cfg["family"])
    margin = float(cfg["deployment"]["token_margin"])
    n = min(int(cfg["deployment"]["check_requests"]), len(outputs))
    if n == 0:
        return {"ok": False, "why": "no finished request to check"}
    rng = np.random.default_rng(seed)
    worst, flips, checked = 0.0, 0, 0
    for i in rng.choice(len(outputs), size=n, replace=False):
        prompt, out = outputs[int(i)]
        if len(out) == 0:
            continue
        seq = np.concatenate([prompt, out]).astype(np.int32)
        pad = -len(seq) % 512
        tokens = jnp.asarray(np.pad(seq, (0, pad)))
        rows = jnp.arange(len(prompt) - 1, len(seq) - 1)
        lg = np.asarray(ref.logits(params, cfg, tokens, rows))
        chosen = lg[np.arange(len(out)), out]
        gap = lg.max(axis=-1) - chosen
        worst = max(worst, float(gap.max()))
        flips += int((gap > 0).sum())
        checked += len(out)
    return {"ok": bool(checked > 0 and worst <= margin),
            "worst_gap": worst, "margin": margin, "tokens_checked": checked,
            "argmax_flips": flips, "requests_checked": int(n)}
