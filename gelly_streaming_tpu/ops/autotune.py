"""Online dispatch autotuner: find the fast (windows-per-dispatch,
K bucket, ingress format) configuration ON the stream actually running.

Every dispatch knob has a STATIC default (ops/triangles._default_chunk
and _tuned_kb, standard ingress): right for reproducibility, wrong for
a stream whose load, skew, or dispatch latency differs from the one
the default was set for — an earlier attachment's chip rows pinned
end-to-end rate at ~500-770K edges/s while the chunk sweep was still
climbing at the compile cap (not current numbers), i.e. the static
pick amortizes dispatch latency worse than the best live pick would.
This module is the runtime's measured selection loop:

- The search space is SMALL and SAFE by construction: every arm is a
  configuration today's kernels already run correctly (wb rungs within
  the compile cap, K rungs on the existing escalation ladder —
  exactness preserved by the overflow recount regardless of K — and
  the two parity-proven wire formats), so an arm change can alter
  TIMING only, never counts.
- Exploration is DETERMINISTIC epsilon-greedy: every
  `explore_period`-th measurement round tries the next single-knob
  move away from the incumbent (coordinate moves, round-robin); all
  other rounds exploit the incumbent. No randomness anywhere — reruns
  take identical decisions on identical timings.
- Promotion has HYSTERESIS: a challenger replaces the incumbent only
  when its smoothed (EMA) edges/s clears `margin` (default the
  repo-wide 1.05 adoption bar) over the incumbent's, so load noise
  cannot flap the configuration.
- The winner PERSISTS to a per-backend tuning cache
  (`~/.cache/gelly_streaming_tpu/tuning_<backend>.json`, override dir
  with GS_TUNE_CACHE) so the second run starts at the first run's
  optimum; the cache is advisory (corrupt/missing files are ignored)
  and seeds only arms inside the current space.

`GS_AUTOTUNE=0` disables everything: callers take their exact legacy
static-gate path, bit-identically (asserted by
tests/operations/test_autotune.py and the chaos autotune leg).

Pre-warm discipline: callers compile an arm's programs (AOT,
`_stream_exec`-style caches) BEFORE its first timed round, so
steady-state streaming still never compiles mid-measurement; arms
never exceed the compile cap (ops/triangles.COMPILE_CAP).
"""

from __future__ import annotations

import json
import os
import threading
from typing import Dict, List, Optional

from ..utils import knobs
from ..utils import telemetry

_DEF_MARGIN = 1.05        # hysteresis: a challenger must win by 5%
_EMA_ALPHA = 0.5          # smoothing of per-arm measured rates
_TIMELINE_CAP = 256       # bound per-tuner event history

_CACHE_LOCK = threading.Lock()


# ----------------------------------------------------------------------
# env knobs (read per call through the utils/knobs registry)
# ----------------------------------------------------------------------
def enabled() -> bool:
    """GS_AUTOTUNE=0 disables the online tuner process-wide; callers
    then run their legacy static-gate path bit-identically."""
    return knobs.get_bool("GS_AUTOTUNE")


def round_chunks() -> int:
    """Dispatch chunks per measurement round (GS_AUTOTUNE_ROUND,
    default 4). The engines run each round as ONE
    ingress_pipeline.run_pipeline call, whose worker pool and depth-2
    overlap only engage past a single item — a 1-chunk round would
    silently measure (and run) the synchronous form, so the default
    keeps several chunks in flight per round; lower it only for
    diagnosis."""
    return knobs.get_int("GS_AUTOTUNE_ROUND")


def explore_period() -> int:
    """Every Nth measurement round is an exploration round
    (GS_AUTOTUNE_EXPLORE, default 3); the rest exploit the
    incumbent."""
    return knobs.get_int("GS_AUTOTUNE_EXPLORE")


def cache_path(backend: str) -> str:
    """Per-backend tuning cache file. GS_TUNE_CACHE overrides the
    DIRECTORY (set it to "0" to disable persistence entirely)."""
    root = knobs.get_path("GS_TUNE_CACHE")
    if root == "0":
        return ""
    if not root:
        root = os.path.join(os.path.expanduser("~"), ".cache",
                            "gelly_streaming_tpu")
    return os.path.join(root, "tuning_%s.json" % backend)


def _backend() -> str:
    try:
        import jax

        return jax.default_backend()
    except Exception:  # gslint: disable=except-hygiene (availability probe: cache filename only, never correctness)
        return "unknown"


def load_cached_best(key: str, backend: str = None) -> Optional[dict]:
    """The persisted best entry {"arm": {...}, "edges_per_s": float}
    for `key`, or None (missing/disabled/corrupt cache — all
    advisory)."""
    path = cache_path(backend or _backend())
    if not path:
        return None
    try:
        with open(path) as f:
            data = json.load(f)
        entry = data.get(key)
        if isinstance(entry, dict) and isinstance(entry.get("arm"),
                                                  dict):
            return entry
    except (OSError, ValueError):
        pass
    return None


def store_best(key: str, arm: dict, edges_per_s: float,
               backend: str = None) -> None:
    """Merge one key's best arm into the cache (atomic replace;
    best-effort — a read-only home never breaks a stream)."""
    path = cache_path(backend or _backend())
    if not path:
        return
    with _CACHE_LOCK:
        try:
            os.makedirs(os.path.dirname(path), exist_ok=True)
            try:
                with open(path) as f:
                    data = json.load(f)
                if not isinstance(data, dict):
                    data = {}
            except (OSError, ValueError):
                data = {}
            data[key] = {"arm": dict(arm),
                         "edges_per_s": round(float(edges_per_s))}
            tmp = "%s.tmp.%d" % (path, os.getpid())
            with open(tmp, "w") as f:
                json.dump(data, f, indent=1, sort_keys=True)
            os.replace(tmp, path)
        except OSError:
            pass


# ----------------------------------------------------------------------
# the tuner
# ----------------------------------------------------------------------
def _akey(arm: dict) -> str:
    """Canonical JSON-able identity of an arm (dict key for EMAs and
    checkpoint state)."""
    return json.dumps(arm, sort_keys=True)


class DispatchTuner:
    """Deterministic epsilon-greedy coordinate search over a small knob
    space, with hysteresis and persistence (module docstring).

    space:   {knob: [ordered values]} — e.g.
             {"wb": [16, 32, 64], "ingress": ["standard", "compact"]}
    initial: the static-gate configuration (one value per knob; values
             must be in the space — the incumbent before any
             measurement, and the arm `GS_AUTOTUNE=0` would run).

    Protocol per measurement round:
        arm = tuner.next_round()      # caller pre-warms arm's programs
        ... run round_chunks dispatch chunks at `arm`, timed ...
        tuner.record(arm, edges, seconds)
    and once per stream: tuner.save().
    """

    def __init__(self, key: str, space: Dict[str, list], initial: dict,
                 margin: float = _DEF_MARGIN, backend: str = None):
        for k, v in initial.items():
            if k not in space or v not in space[k]:
                raise ValueError(
                    "initial %s=%r outside tuning space %r"
                    % (k, v, space.get(k)))
        self.key = key
        self.space = {k: list(vs) for k, vs in space.items()}
        self.margin = float(margin)
        self.backend = backend or _backend()
        self.incumbent = dict(initial)
        self._ema: Dict[str, float] = {}
        self._round = 0
        self._promotions = 0
        self._explore_cursor = 0
        self.timeline: List[dict] = []
        cached = load_cached_best(key, self.backend)
        if cached and self._in_space(cached["arm"]) \
                and cached["arm"] != self.incumbent:
            # the previous run's optimum: start there (the whole point
            # of persistence); it stays on probation like any incumbent
            self.incumbent = {k: cached["arm"][k] for k in self.space}
            self._event("cache_seed", self.incumbent, None)

    # -- helpers -------------------------------------------------------
    def _in_space(self, arm: dict) -> bool:
        return all(k in arm and arm[k] in vs
                   for k, vs in self.space.items())

    def _candidates(self) -> List[dict]:
        """Single-knob moves away from the incumbent, in deterministic
        knob-name order, nearest values first (down then up)."""
        out = []
        for k in sorted(self.space):
            vs = self.space[k]
            i = vs.index(self.incumbent[k])
            for j in (i - 1, i + 1):
                if 0 <= j < len(vs):
                    cand = dict(self.incumbent)
                    cand[k] = vs[j]
                    out.append(cand)
        return out

    def _event(self, action: str, arm: dict, rate) -> None:
        self.timeline.append({
            "round": self._round, "action": action, "arm": dict(arm),
            "edges_per_s": None if rate is None else round(rate)})
        if len(self.timeline) > _TIMELINE_CAP:
            del self.timeline[:len(self.timeline) - _TIMELINE_CAP]
        # every scheduler decision is a structured flight-recorder
        # event (promotions durably: a mid-stream configuration change
        # is exactly what a post-mortem must be able to date)
        telemetry.event("autotune." + action,
                        durable=action == "promote",
                        key=self.key, round=self._round,
                        arm=json.dumps(arm, sort_keys=True),
                        edges_per_s=None if rate is None
                        else round(rate))

    # -- protocol ------------------------------------------------------
    def next_round(self) -> dict:
        """The arm the next measurement round runs: the incumbent,
        except on every `explore_period()`-th round, where the next
        coordinate move is probed (round-robin over the candidate
        list). Deterministic in the round counter."""
        cands = self._candidates()
        if not cands or (self._round + 1) % explore_period():
            return dict(self.incumbent)
        arm = cands[self._explore_cursor % len(cands)]
        self._explore_cursor += 1
        return arm

    def record(self, arm: dict, edges: int, seconds: float) -> None:
        """Fold one round's measured rate into the arm's EMA; promote
        the arm over the incumbent only when its EMA clears the
        hysteresis margin (never on the first observation of a
        challenger — one lucky draw must not flip the config)."""
        if seconds <= 0 or edges <= 0:
            return
        rate = edges / seconds
        self._round += 1
        k = _akey(arm)
        seen = k in self._ema
        self._ema[k] = (rate if not seen
                        else (1 - _EMA_ALPHA) * self._ema[k]
                        + _EMA_ALPHA * rate)
        explored = arm != self.incumbent
        promoted = False
        inc_ema = self._ema.get(_akey(self.incumbent))
        if explored and seen and inc_ema is not None \
                and self._ema[k] >= self.margin * inc_ema:
            self.incumbent = dict(arm)
            self._promotions += 1
            self._explore_cursor = 0
            promoted = True
        self._event("promote" if promoted
                    else ("explore" if explored else "exploit"),
                    arm, rate)

    def rekey(self, key: str, space: Dict[str, list] = None,
              initial: dict = None) -> None:
        """Adopt a new cache identity mid-stream (bucket growth changed
        the shapes the rates were measured at): EMAs reset — they
        described the old shapes — while the incumbent survives as the
        prior (clamped to `initial` if the new `space` dropped it), and
        the new key's persisted best, if any, re-seeds it. Keeps the
        tuner object (and its checkpointed continuity) alive across
        O(log V) bucket doublings instead of discarding learned state."""
        self.key = key
        if space is not None:
            self.space = {k: list(vs) for k, vs in space.items()}
        if not self._in_space(self.incumbent):
            if initial is None or not self._in_space(initial):
                raise ValueError(
                    "rekey needs an in-space initial when the "
                    "incumbent %r left the space" % (self.incumbent,))
            self.incumbent = dict(initial)
        self._ema = {}
        self._explore_cursor = 0
        cached = load_cached_best(key, self.backend)
        if cached and self._in_space(cached["arm"]):
            self.incumbent = {k: cached["arm"][k] for k in self.space}
        self._event("rekey", self.incumbent, None)

    def best(self) -> dict:
        return dict(self.incumbent)

    def best_rate(self) -> Optional[float]:
        return self._ema.get(_akey(self.incumbent))

    def save(self) -> None:
        """Persist the incumbent (with its smoothed rate) so the next
        process seeds from it."""
        rate = self.best_rate()
        if rate:
            store_best(self.key, self.incumbent, rate, self.backend)

    # -- observability / checkpointing --------------------------------
    def summary(self) -> dict:
        """Provenance row for bench/profiler output: the chosen knobs
        plus the decision timeline (bounded)."""
        return {
            "key": self.key,
            "chosen": dict(self.incumbent),
            "rounds": self._round,
            "promotions": self._promotions,
            "edges_per_s_ema": (None if self.best_rate() is None
                                else round(self.best_rate())),
            "timeline": [dict(e) for e in self.timeline[-32:]],
        }

    def state_dict(self) -> dict:
        """JSON/npz-able tuning state (rides engine/driver checkpoints
        so a resumed stream keeps its learned configuration). The
        cache `key` is deliberately NOT state: it is the tuner's
        identity at its current buckets, and a resume after bucket
        growth restores the learned values into the new identity."""
        return {
            "incumbent": dict(self.incumbent),
            "ema": [[k, float(v)] for k, v in sorted(self._ema.items())],
            "round": int(self._round),
            "promotions": int(self._promotions),
            "explore_cursor": int(self._explore_cursor),
        }

    def load_state_dict(self, state: dict) -> None:
        """Adopt checkpointed tuning state; entries outside the current
        space are dropped (a resume across a code change must not pin
        an arm that no longer exists)."""
        inc = state.get("incumbent")
        if isinstance(inc, dict) and self._in_space(inc):
            self.incumbent = {k: inc[k] for k in self.space}
        self._ema = {str(k): float(v)
                     for k, v in state.get("ema", [])}
        self._round = int(state.get("round", 0))  # gslint: disable=host-sync (checkpoint payloads are host scalars, never device values)
        self._promotions = int(state.get("promotions", 0))  # gslint: disable=host-sync (checkpoint payloads are host scalars, never device values)
        self._explore_cursor = int(state.get("explore_cursor", 0))  # gslint: disable=host-sync (checkpoint payloads are host scalars, never device values)
