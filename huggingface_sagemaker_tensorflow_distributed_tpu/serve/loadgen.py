"""Open-loop load generation + deadline-aware driving (ISSUE 16).

Every serving number the repo produced before this module came from a
CLOSED loop: submit a fixed trace, run to completion. A closed loop
self-throttles — the engine's own backpressure slows the offered load —
so it structurally cannot exhibit queueing collapse, and "requests/sec
at an SLO" has no honest denominator. This module is the open-loop
half: requests arrive on a SCHEDULE that does not care how busy the
engine is, each carries a deadline (:class:`SloSpec`), and the driver
measures the DistServe goodput question — what fraction of arrivals
met their TTFT/TPOT targets at this arrival rate.

Three layers, in the house determinism style:

- **Arrival processes** — seeded stdlib-``random`` generators
  (:func:`poisson_arrivals`, :func:`bursty_arrivals` — a two-state
  Markov-modulated Poisson process whose bursts are what actually
  breaks p99s in production traces) plus bounded-Pareto
  :func:`heavy_tailed_lengths` for prompt/output sizing.
  :func:`make_schedule` composes them into ``(arrival_s,
  request_spec)`` rows — pure functions of their seeds, so every
  schedule is replayable byte-for-byte.
- **:class:`OpenLoopDriver`** — submits a schedule through a
  :class:`~.router.Router` or a bare :class:`~.engine.ServeEngine` in
  one of two clock modes. ``virtual`` interleaves arrivals with engine
  iterations on a deterministic virtual clock (``tick_s`` of virtual
  time per fleet step): token streams, backlog integers, and the
  driver's own attainment/miss-attribution accounting are exact across
  reruns — what the tier-1 tests run on a shared CPU.
  ``wall`` honors arrival times with real sleeps and threads
  ``arrival_s``/``slo`` into :meth:`~.engine.ServeEngine.submit`, so
  the engine stamps real verdicts into the telemetry stream — the mode
  ``obsctl goodput`` replays, banked for hardware.
- **Knob parsing** — ``--arrival poisson:2.0 | bursty:4,0.5,0.25 |
  closed`` (:func:`parse_arrival`, env ``HSTD_SERVE_ARRIVAL`` +
  ``HSTD_SERVE_ARRIVAL_SEED``) and ``--slo ttft:0.5,tpot:0.05``
  (:func:`parse_slo`, env ``HSTD_SERVE_SLO_TTFT_S`` /
  ``HSTD_SERVE_SLO_TPOT_S``), mirrored by ``scripts/serve.py``.

The driver stamps each run with ONE ``serve`` ``open_loop`` event
(process / rate / clock / request count / targets) so a downstream
``obsctl goodput`` replay can split a rate sweep's merged stream back
into its runs.
"""

from __future__ import annotations

import os
import random
import time
from dataclasses import dataclass
from typing import Optional, Sequence

from huggingface_sagemaker_tensorflow_distributed_tpu import obs
from huggingface_sagemaker_tensorflow_distributed_tpu.serve.scheduler import (
    WAITING,
    Request,
)

ENV_ARRIVAL = "HSTD_SERVE_ARRIVAL"
ENV_ARRIVAL_SEED = "HSTD_SERVE_ARRIVAL_SEED"
ENV_SLO_TTFT = "HSTD_SERVE_SLO_TTFT_S"
ENV_SLO_TPOT = "HSTD_SERVE_SLO_TPOT_S"

PROCESSES = ("poisson", "bursty")
CLOCKS = ("virtual", "wall")

# driver miss-attribution phases, coarser than the PR 10 five-way split
# on purpose: the virtual clock can only observe SCHEDULER transitions
# (arrival -> admit -> first token -> finish), and queue-vs-service is
# the decision boundary capacity planning acts on. Order is the
# tie-break (earlier phase wins a tie, matching obs.timeline).
MISS_PHASES = ("queue", "prefill", "decode")


@dataclass(frozen=True)
class SloSpec:
    """Per-request deadline targets, in seconds (None = no target on
    that axis; at least one must be set). ``ttft_s`` bounds time to
    first token FROM ARRIVAL; ``tpot_s`` bounds the mean inter-token
    time over the post-first-token tail. Duck-typed by
    :meth:`~.engine.ServeEngine.submit` (the engine never imports this
    module), frozen so a single spec can be shared across a whole
    schedule."""

    ttft_s: Optional[float] = None
    tpot_s: Optional[float] = None

    def __post_init__(self):
        if self.ttft_s is None and self.tpot_s is None:
            raise ValueError("SloSpec needs at least one target "
                             "(ttft_s and/or tpot_s)")
        for name in ("ttft_s", "tpot_s"):
            v = getattr(self, name)
            if v is not None and not float(v) > 0:
                raise ValueError(f"SloSpec.{name} must be > 0, got {v!r}")


# -- knob parsing ------------------------------------------------------------


def parse_arrival(spec=None):
    """The arrival-process knob: ``closed`` (None — the pre-open-loop
    submit-everything trace), ``poisson:RATE`` (requests/sec), or
    ``bursty:RATE_HI,RATE_LO,P_SWITCH`` (two-state Markov-modulated
    Poisson: gaps draw at the current state's rate, the state flips
    with probability ``p_switch`` after each arrival). None reads
    ``HSTD_SERVE_ARRIVAL`` (default ``closed``). Returns None or
    ``(process, params_dict)``."""
    if spec is None:
        spec = os.environ.get(ENV_ARRIVAL, "closed") or "closed"
    s = str(spec).strip().lower()
    if s in ("", "closed"):
        return None
    name, _, argstr = s.partition(":")
    try:
        if name == "poisson":
            rate = float(argstr)
            if not rate > 0:
                raise ValueError
            return ("poisson", {"rate": rate})
        if name == "bursty":
            hi, lo, p = (float(x) for x in argstr.split(","))
            if not (hi > 0 and lo > 0 and 0 <= p <= 1):
                raise ValueError
            return ("bursty", {"rate_hi": hi, "rate_lo": lo,
                               "p_switch": p})
    except ValueError:
        pass
    raise ValueError(
        f"unparseable {ENV_ARRIVAL} value {spec!r}: expected "
        "closed | poisson:RATE | bursty:RATE_HI,RATE_LO,P_SWITCH")


def parse_arrival_seed(spec=None) -> int:
    """The schedule seed knob: any int. None reads
    ``HSTD_SERVE_ARRIVAL_SEED`` (default 0)."""
    if spec is None:
        spec = os.environ.get(ENV_ARRIVAL_SEED, "0") or "0"
    try:
        return int(str(spec).strip() or "0")
    except ValueError:
        raise ValueError(f"unparseable {ENV_ARRIVAL_SEED} value "
                         f"{spec!r}: expected an integer")


def parse_slo(spec=None) -> Optional[SloSpec]:
    """The deadline knob: ``ttft:SECS[,tpot:SECS]`` in either order,
    or ``none``. None reads ``HSTD_SERVE_SLO_TTFT_S`` /
    ``HSTD_SERVE_SLO_TPOT_S`` (both unset = no SLO — every new
    telemetry field stays absent, the byte-identity contract)."""
    if spec is None:
        ttft = os.environ.get(ENV_SLO_TTFT, "") or None
        tpot = os.environ.get(ENV_SLO_TPOT, "") or None
        if ttft is None and tpot is None:
            return None
        try:
            return SloSpec(
                ttft_s=float(ttft) if ttft is not None else None,
                tpot_s=float(tpot) if tpot is not None else None)
        except ValueError as e:
            raise ValueError(f"unparseable {ENV_SLO_TTFT}/"
                             f"{ENV_SLO_TPOT} values: {e}")
    s = str(spec).strip().lower()
    if s in ("", "none"):
        return None
    kw = {}
    try:
        for part in s.split(","):
            axis, _, val = part.partition(":")
            axis = axis.strip()
            if axis not in ("ttft", "tpot") or f"{axis}_s" in kw:
                raise ValueError
            kw[f"{axis}_s"] = float(val)
        return SloSpec(**kw)
    except ValueError:
        raise ValueError(f"unparseable SLO spec {spec!r}: expected "
                         "ttft:SECS[,tpot:SECS] | none")


# -- arrival processes + length sampling -------------------------------------


def poisson_arrivals(rate: float, n: int, seed: int = 0) -> list:
    """``n`` arrival offsets (seconds from schedule start) with
    exponential inter-arrival gaps at ``rate`` requests/sec — a pure
    function of ``(rate, n, seed)``."""
    if not rate > 0:
        raise ValueError(f"rate must be > 0, got {rate!r}")
    rng = random.Random(seed)
    t, out = 0.0, []
    for _ in range(n):
        t += rng.expovariate(rate)
        out.append(t)
    return out


def bursty_arrivals(rate_hi: float, rate_lo: float, p_switch: float,
                    n: int, seed: int = 0) -> list:
    """Two-state Markov-modulated Poisson arrivals: each gap draws at
    the current state's rate (starting hot), and the state flips with
    probability ``p_switch`` after every arrival — mean burst length
    ``1/p_switch`` requests. The burst/lull alternation is what drives
    transient backlogs (and p99 TTFT) that a rate-matched plain
    Poisson stream never shows."""
    if not (rate_hi > 0 and rate_lo > 0):
        raise ValueError("rates must be > 0")
    if not 0 <= p_switch <= 1:
        raise ValueError(f"p_switch must be in [0, 1], got {p_switch!r}")
    rng = random.Random(seed)
    hot, t, out = True, 0.0, []
    for _ in range(n):
        t += rng.expovariate(rate_hi if hot else rate_lo)
        out.append(t)
        if rng.random() < p_switch:
            hot = not hot
    return out


def heavy_tailed_lengths(n: int, lo: int, hi: int, seed: int = 0,
                         alpha: float = 1.5) -> list:
    """``n`` bounded-Pareto(``alpha``) lengths in ``[lo, hi]``: mass
    near ``lo`` with an occasional near-``hi`` outlier — the
    production-trace shape (most prompts short, a few huge) whose
    stragglers dominate queueing behavior. Smaller ``alpha`` =
    heavier tail."""
    if not 0 < lo <= hi:
        raise ValueError(f"need 0 < lo <= hi, got ({lo}, {hi})")
    if not alpha > 0:
        raise ValueError(f"alpha must be > 0, got {alpha!r}")
    rng = random.Random(seed)
    out = []
    for _ in range(n):
        u = max(rng.random(), 1e-12)
        out.append(int(min(hi, max(lo, round(lo / u ** (1.0 / alpha))))))
    return out


def make_schedule(n_requests: int, vocab_size: int, *,
                  process: str = "poisson", rate: float = 1.0,
                  rate_lo: Optional[float] = None,
                  p_switch: float = 0.1, seed: int = 0,
                  prompt_lo: int = 4, prompt_hi: int = 32,
                  new_lo: int = 4, new_hi: int = 32,
                  alpha: float = 1.5,
                  eos_token_id: Optional[int] = None,
                  groups: Sequence[str] = (),
                  deadline_s: Optional[float] = None,
                  priorities: Sequence[int] = ()) -> list:
    """Compose an arrival process with heavy-tailed prompt/output
    lengths into ``[(arrival_s, spec), ...]`` sorted by arrival, where
    each spec is ``{"prompt": [ids], "max_new_tokens": n, "group":
    tag?}`` — exactly the keys :meth:`OpenLoopDriver.run` forwards to
    ``submit``. Prompts avoid ``eos_token_id``; ``groups`` (tenants)
    round-robin over arrivals, as do ``priorities`` (ISSUE 20
    admission classes, smaller = more urgent); ``deadline_s`` stamps
    rows with an end-to-end deadline — a scalar stamps every row, a
    sequence round-robins aligned with ``priorities``/``groups`` (the
    per-class-deadline shape the admission bench drives). Pure in ``seed``: the
    same call is the same schedule, which is what the replay-identity
    gates rest on."""
    if process == "poisson":
        arrivals = poisson_arrivals(rate, n_requests, seed)
    elif process == "bursty":
        arrivals = bursty_arrivals(
            rate, rate_lo if rate_lo is not None else rate / 4.0,
            p_switch, n_requests, seed)
    else:
        raise ValueError(f"unknown arrival process {process!r}: "
                         f"expected {' | '.join(PROCESSES)}")
    rng = random.Random(f"{seed}:lengths")
    plens = heavy_tailed_lengths(n_requests, prompt_lo, prompt_hi,
                                 seed=rng.randrange(1 << 30), alpha=alpha)
    nlens = heavy_tailed_lengths(n_requests, new_lo, new_hi,
                                 seed=rng.randrange(1 << 30), alpha=alpha)
    tok_rng = random.Random(f"{seed}:tokens")
    out = []
    for i, arrival in enumerate(arrivals):
        prompt = []
        while len(prompt) < plens[i]:
            tok = tok_rng.randrange(vocab_size)
            if tok != eos_token_id:
                prompt.append(tok)
        spec = {"prompt": prompt, "max_new_tokens": nlens[i]}
        if groups:
            spec["group"] = groups[i % len(groups)]
        if deadline_s is not None:
            spec["deadline_s"] = float(
                deadline_s[i % len(deadline_s)]
                if isinstance(deadline_s, (list, tuple)) else deadline_s)
        if priorities:
            spec["priority"] = int(priorities[i % len(priorities)])
        out.append((arrival, spec))
    return out


# -- the driver --------------------------------------------------------------

_SPEC_KEYS = ("temperature", "top_k", "top_p", "seed", "group",
              "deadline_s", "priority")


class OpenLoopDriver:
    """Submit a ``[(arrival_s, spec), ...]`` schedule through a target
    (:class:`~.router.Router` or bare :class:`~.engine.ServeEngine` —
    anything with ``submit/step/has_work/warmup/run``) honoring arrival
    times, then drain.

    ``clock="virtual"``: arrivals interleave with engine iterations on
    a driver-owned virtual clock — each fleet step advances it by
    ``tick_s`` virtual seconds, idle time jumps to the next arrival —
    and the driver polls scheduler transitions after every step to
    stamp virtual admit/first-token/finish times. All accounting
    (:meth:`summary`: attainment, per-group split, per-phase miss
    attribution) is then a pure function of (schedule, tokens,
    iteration count): deterministic on a noisy shared CPU, which is
    what lets tier-1 gates assert exact figures. The SLO spec is NOT
    forwarded to the engine in this mode — wall-domain verdicts would
    be nondeterministic booleans in the event stream — but
    ``arrival_s`` is, so the deterministic ``arrival_backlog`` ledger
    rider and backlog peak still appear.

    ``clock="wall"``: real sleeps to each arrival, ``arrival_s`` AND
    ``slo`` threaded into ``submit`` — the engine stamps real verdicts
    into finish events and its report carries real attainment; the
    stream ``obsctl goodput`` replays. Warmup runs BEFORE the clock
    starts in both modes so compile time never lands in a TTFT.
    """

    def __init__(self, target, schedule, *, clock: str = "virtual",
                 tick_s: float = 0.001, slo: Optional[SloSpec] = None,
                 process: str = "custom", rate: Optional[float] = None):
        if clock not in CLOCKS:
            raise ValueError(f"unknown clock {clock!r}: expected "
                             f"{' | '.join(CLOCKS)}")
        if not tick_s > 0:
            raise ValueError(f"tick_s must be > 0, got {tick_s!r}")
        self.target = target
        # stable sort: simultaneous arrivals keep schedule order
        self.schedule = sorted(schedule, key=lambda row: row[0])
        self.clock = clock
        self.tick_s = float(tick_s)
        self.slo = slo
        self.process = str(process)
        self.rate = rate
        self._recs: list[dict] = []
        self._ran = False

    # -- submission ----------------------------------------------------------

    def _submit(self, arrival: float, spec: dict, t0: float):
        kw = {k: spec[k] for k in _SPEC_KEYS if k in spec}
        req = self.target.submit(
            spec["prompt"], spec["max_new_tokens"],
            arrival_s=t0 + arrival,
            slo=self.slo if self.clock == "wall" else None, **kw)
        if getattr(req, "rejected", False):
            # structured rate-limit rejection (ISSUE 20): recorded —
            # never a silent drop — but excluded from service-time
            # accounting, because the request was refused, not served
            self._recs.append({"arrival": arrival,
                               "group": spec.get("group", ""),
                               "rejected": True})
            return req
        rec = {"arrival": arrival, "req": req,
               "group": spec.get("group", "")}
        if "deadline_s" in spec:
            rec["deadline_s"] = float(spec["deadline_s"])
        self._recs.append(rec)
        return req

    # -- clock loops ---------------------------------------------------------

    def _poll(self, vt: float) -> None:
        """Stamp virtual times for every scheduler transition since the
        last step: queue->resident (admit), first emitted token,
        finish. A request that crossed several transitions within one
        iteration stamps them all at this tick — per-iteration
        granularity is the virtual clock's resolution."""
        for rec in self._recs:
            if "v_finish" in rec or "req" not in rec:
                continue
            req = rec["req"]
            if "v_admit" not in rec and req.state != WAITING:
                rec["v_admit"] = vt
            if "v_first" not in rec and req.first_token_t is not None:
                rec["v_first"] = vt
            if req.finish_t is not None:
                rec["v_finish"] = vt

    def _set_policy_clock(self, now: float) -> None:
        """Pin every scheduler's admission-policy clock to the virtual
        timeline (``t0 + vt``, the same domain ``arrival_s`` is stamped
        in) so aging promotions under ``policy="slo"`` are a pure
        function of the schedule — deterministic on a noisy host. Wall
        mode leaves the clock unpinned (``perf_counter`` truth)."""
        for eng in getattr(self.target, "engines", None) or [self.target]:
            sched = getattr(eng, "sched", None)
            if sched is not None:
                sched.policy_now = now

    def _run_virtual(self, t0: float) -> None:
        idx, vt = 0, 0.0
        while idx < len(self.schedule) or self.target.has_work():
            if (idx < len(self.schedule) and not self.target.has_work()
                    and vt < self.schedule[idx][0]):
                # idle: jump straight to the next arrival — virtual
                # time never burns host iterations on an empty fleet
                vt = self.schedule[idx][0]
            while (idx < len(self.schedule)
                   and self.schedule[idx][0] <= vt):
                arrival, spec = self.schedule[idx]
                idx += 1
                self._submit(arrival, spec, t0)
            if self.target.has_work():
                self._set_policy_clock(t0 + vt)
                self.target.step()
                vt += self.tick_s
                self._poll(vt)

    def _run_wall(self, t0: float) -> None:
        idx = 0
        while idx < len(self.schedule):
            now = time.perf_counter() - t0
            arrival, spec = self.schedule[idx]
            if arrival <= now:
                self._submit(arrival, spec, t0)
                idx += 1
            elif self.target.has_work():
                # serve resident work while the next arrival is in the
                # future — the open-loop property: waiting for work to
                # drain never delays an arrival, but an idle engine
                # never spins either
                self.target.step()
            else:
                time.sleep(min(arrival - now, 0.05))

    def run(self) -> dict:
        """Drive the schedule to completion; returns the target's
        merged ``{rid: Request}``. Emits one ``open_loop`` stamp event
        up front, then the target's own ``run()`` drains the tail and
        emits the report event (which carries attainment/backlog when
        the run threaded targets/arrivals)."""
        if self._ran:
            raise RuntimeError("OpenLoopDriver.run() is one-shot: "
                               "build a fresh driver per run")
        self._ran = True
        extra = {}
        if self.rate is not None:
            extra["rate"] = float(self.rate)
        if self.slo is not None:
            if self.slo.ttft_s is not None:
                extra["slo_ttft_s"] = float(self.slo.ttft_s)
            if self.slo.tpot_s is not None:
                extra["slo_tpot_s"] = float(self.slo.tpot_s)
        obs.serve("open_loop", process=self.process, clock=self.clock,
                  requests=len(self.schedule), **extra)
        sampled = any(spec.get("temperature", 0) > 0
                      for _, spec in self.schedule)
        self.target.warmup(sampled=sampled)
        t0 = time.perf_counter()
        if self.clock == "virtual":
            self._run_virtual(t0)
        else:
            self._run_wall(t0)
        finished = self.target.run()
        if self.clock == "virtual":
            # anything the loop's last poll missed (run() drained it)
            # stamps at one tick past the loop's horizon
            vt = max((rec.get("v_finish", 0.0) for rec in self._recs),
                     default=0.0) + self.tick_s
            self._poll(vt)
        return finished

    # -- accounting ----------------------------------------------------------

    def _virtual_phases(self, rec: dict) -> dict:
        """The coarse queue/prefill/decode split of one request's
        virtual lifetime (arrival -> admit -> first token -> finish)."""
        admit = rec.get("v_admit", rec.get("v_finish", rec["arrival"]))
        first = rec.get("v_first", rec.get("v_finish", admit))
        return {
            "queue": max(admit - rec["arrival"], 0.0),
            "prefill": max(first - admit, 0.0),
            "decode": max(rec.get("v_finish", first) - first, 0.0),
        }

    def _generated(self, req: Request) -> int:
        return (len(req.prompt) - req.orig_prompt_len) + len(req.output)

    def summary(self) -> dict:
        """The run's goodput accounting — deterministic in virtual
        mode, wall-truth otherwise. Keys: ``requests``/``process``/
        ``clock`` always; with an SLO also ``slo_attainment``,
        ``slo_met``/``slo_missed`` counts, ``group_slo_attainment``,
        ``miss_phases`` (miss count per dominant phase) and
        ``dominant_miss_phase`` (None with zero misses); plus
        ``goodput_tokens`` — tokens generated by deadline-meeting
        requests, the DistServe goodput numerator. In virtual mode the
        summary also carries ``ttft_p50/p95/p99_s`` and
        ``tpot_p50/p95/p99_s`` over the virtual timeline — the
        deterministic per-side attribution the disagg bench gates read
        (TTFT is the prefill side's figure, TPOT the decode side's).
        Structured rate-limit rejections surface as ``rate_limited``
        and are excluded from attainment (refused, not served late);
        schedules carrying ``deadline_s`` add ``deadline_misses`` /
        ``deadline_miss_frac`` — deterministic virtual-timeline
        verdicts, the admission bench's strictly-lower gate (ISSUE
        20)."""
        out: dict = {"requests": len(self._recs), "clock": self.clock,
                     "process": self.process}
        if self.rate is not None:
            out["rate"] = self.rate
        served = [rec for rec in self._recs if "req" in rec]
        if len(served) < len(self._recs):
            out["rate_limited"] = len(self._recs) - len(served)
        if self.clock == "virtual":
            from huggingface_sagemaker_tensorflow_distributed_tpu.obs.report import (  # noqa: E501
                percentile,
            )
            ttfts = sorted(rec["v_first"] - rec["arrival"]
                           for rec in self._recs if "v_first" in rec)
            tpots = sorted(
                (rec["v_finish"] - rec["v_first"])
                / max(self._generated(rec["req"]) - 1, 1)
                for rec in self._recs
                if "v_first" in rec and "v_finish" in rec)
            for label, vals in (("ttft", ttfts), ("tpot", tpots)):
                if vals:
                    out[f"{label}_p50_s"] = round(percentile(vals, 0.50), 6)
                    out[f"{label}_p95_s"] = round(percentile(vals, 0.95), 6)
                    out[f"{label}_p99_s"] = round(percentile(vals, 0.99), 6)
        dl_recs = [rec for rec in served if "deadline_s" in rec]
        if dl_recs:
            # end-to-end deadline verdicts: wall mode trusts the
            # engine's stamped verdict, virtual mode recomputes on the
            # driver's deterministic timeline (the engine's verdict is
            # perf_counter truth, which would be noisy here)
            if self.clock == "wall":
                misses = sum(1 for rec in dl_recs
                             if rec["req"].deadline_miss)
            else:
                misses = sum(
                    1 for rec in dl_recs
                    if rec.get("v_finish", float("inf")) - rec["arrival"]
                    > rec["deadline_s"])
            out["deadline_misses"] = misses
            out["deadline_miss_frac"] = round(misses / len(dl_recs), 4)
        if self.slo is None:
            return out
        met = 0
        goodput_tokens = 0
        groups: dict = {}
        miss_phases = dict.fromkeys(MISS_PHASES, 0)
        for rec in served:
            req = rec["req"]
            if self.clock == "wall":
                ok = bool(req.slo_met)
            else:
                ok = True
                tokens = self._generated(req)
                if self.slo.ttft_s is not None:
                    first = rec.get("v_first")
                    ok &= (first is not None
                           and first - rec["arrival"] <= self.slo.ttft_s)
                if self.slo.tpot_s is not None:
                    first = rec.get("v_first")
                    finish = rec.get("v_finish")
                    ok &= (first is not None and finish is not None
                           and (finish - first) / max(tokens - 1, 1)
                           <= self.slo.tpot_s)
            met += int(ok)
            if ok:
                goodput_tokens += self._generated(req)
            else:
                if self.clock == "wall":
                    phases = {ph: req.phase_s.get(ph, 0.0)
                              for ph in MISS_PHASES}
                    # fold pre-submit backlog + preemption stalls into
                    # queue: from the deadline's point of view, both
                    # are time spent not being served
                    if req.arrival_s is not None and req.submit_t:
                        phases["queue"] += max(
                            req.submit_t - req.arrival_s, 0.0)
                    phases["queue"] += req.phase_s.get("preempted", 0.0)
                else:
                    phases = self._virtual_phases(rec)
                dom = max(MISS_PHASES,
                          key=lambda ph: (phases[ph],
                                          -MISS_PHASES.index(ph)))
                miss_phases[dom] += 1
            acc = groups.setdefault(rec["group"], [0, 0])
            acc[0] += int(ok)
            acc[1] += 1
        total = len(served)
        out["slo_met"] = met
        out["slo_missed"] = total - met
        out["slo_attainment"] = round(met / total, 4) if total else 0.0
        out["goodput_tokens"] = goodput_tokens
        out["group_slo_attainment"] = {
            g: round(m / t, 4) for g, (m, t) in sorted(groups.items())
            if t}
        out["miss_phases"] = {ph: n for ph, n in miss_phases.items()
                              if n}
        misses = [(n, ph) for ph, n in miss_phases.items() if n]
        out["dominant_miss_phase"] = (
            max(misses, key=lambda x: (x[0], -MISS_PHASES.index(x[1])))[1]
            if misses else None)
        return out
