"""Shared bounded-retry / backoff policy (stdlib-only, standalone-loadable).

Before round 14 every cross-host interaction hand-rolled its own retry shape:
the fleet scoreboard doubled a poll interval inline, ``HeartbeatClient``
re-beat a dead router at a fixed cadence (a hot loop when the interval is
short), the router's monitor re-dispatched queued prompts on every sweep,
and ``scripts/loadgen.py`` polled ``/history`` at a flat 50 ms. One policy
object replaces all of them:

- **bounded exponential backoff** — ``base_s * multiplier**attempt`` capped
  at ``cap_s`` (never unbounded: a dead peer costs one socket timeout per
  window, not per scheduling decision);
- **deterministic jitter** — the jitter fraction comes from
  ``md5(key, attempt)``, not ``random``: two runs of one seeded chaos
  schedule retry at identical instants (the reproducibility contract
  scripts/chaos.py gates on), while distinct keys still de-synchronize so a
  fleet of backends never thunders the router in lockstep;
- **deadline cap** — ``give up at`` an absolute budget regardless of the
  attempt count, so a retry loop can never outlive the request it serves.

Module level is stdlib-only and free of package-relative imports by the
``utils/roofline.py`` contract: jax-free scripts (loadgen, chaos) load this
file standalone by path.
"""

from __future__ import annotations

import dataclasses
import hashlib
import time


def deterministic_jitter(key: str, attempt: int) -> float:
    """A stable value in [0, 1) from (key, attempt) — the jitter source.
    md5, not ``hash()``: process-salted hashes would make two runs of one
    seeded schedule back off at different instants."""
    digest = hashlib.md5(f"{key}:{attempt}".encode()).digest()
    return int.from_bytes(digest[:8], "big") / float(1 << 64)


@dataclasses.dataclass(frozen=True)
class RetryPolicy:
    """Bounded exponential backoff + deterministic jitter + deadline cap.

    ``backoff_s(attempt, key)`` is the pure schedule (attempt 0 = the wait
    after the FIRST failure); ``attempts()`` iterates it with sleeping;
    ``call()`` wraps a callable. ``jitter`` is the fraction of each window
    that jitters DOWNWARD (full windows stay the worst case, so caps and
    deadline math read literally)."""

    max_attempts: int = 4
    base_s: float = 0.1
    cap_s: float = 30.0
    multiplier: float = 2.0
    jitter: float = 0.5
    deadline_s: float | None = None

    def backoff_s(self, attempt: int, key: str = "") -> float:
        # Exponent clamped: float ** raises OverflowError past ~2**1024, and
        # callers legitimately pass unbounded consecutive-failure counts (a
        # heartbeat client surviving hours of router downtime must not have
        # its loop die computing its own sleep). 64 doublings exceed any cap.
        raw = min(self.cap_s,
                  self.base_s * self.multiplier ** min(max(0, attempt), 64))
        if not self.jitter:
            return raw
        return raw * (1.0 - self.jitter * deterministic_jitter(key, attempt))

    def attempts(self, key: str = "", sleep=time.sleep, now=time.monotonic):
        """Yield attempt indices 0..max_attempts-1, sleeping the backoff
        between attempts and stopping early at the deadline. The caller
        ``break``s on success; exhausting the generator means giving up."""
        t0 = now()
        for attempt in range(self.max_attempts):
            yield attempt
            if attempt + 1 >= self.max_attempts:
                return
            wait = self.backoff_s(attempt, key)
            if self.deadline_s is not None:
                remaining = self.deadline_s - (now() - t0)
                if remaining <= 0:
                    return
                wait = min(wait, remaining)
            sleep(wait)

    def call(self, fn, *, retry_on=(OSError,), key: str = "",
             sleep=time.sleep, now=time.monotonic):
        """Run ``fn()`` under the policy; returns its first successful value
        or re-raises the LAST failure once the budget (attempts or deadline)
        is spent. Only ``retry_on`` exception types are retried — anything
        else propagates immediately (a 400 is not a transient)."""
        last: BaseException | None = None
        for _attempt in self.attempts(key=key, sleep=sleep, now=now):
            try:
                return fn()
            except retry_on as e:  # noqa: PERF203 — the retry loop is the point
                last = e
        if last is None:  # max_attempts <= 0: nothing ever ran
            raise ValueError(f"retry budget empty ({self.max_attempts} attempts)")
        raise last


# Shared instances: ONE place the fleet's retry shapes are defined, so an
# operator reasons about one table instead of five hand-rolled loops.
# (Callers needing different bounds derive with dataclasses.replace.)
HEARTBEAT = RetryPolicy(max_attempts=1_000_000, base_s=0.5, cap_s=30.0)
POLL = RetryPolicy(max_attempts=1_000_000, base_s=0.05, cap_s=0.5, jitter=0.25)
DISPATCH = RetryPolicy(max_attempts=4, base_s=0.1, cap_s=5.0)
