"""Profiling & timing: honest numbers on an async dispatch runtime.

The reference's entire observability story is one ``time.time()`` pair around
a single call (``/root/reference/model.py:149-153``) — which on an async
runtime like JAX would time the *dispatch*, not the work. Here every timing
fences with ``jax.block_until_ready`` and reports robust statistics, device
memory stats expose peak HBM, and ``trace`` wraps ``jax.profiler`` capture
(TensorBoard/Perfetto) as SURVEY.md §5 mandates.
"""

from __future__ import annotations

import contextlib
import dataclasses
import statistics
import time
from typing import Any, Callable, Dict, Iterator, Optional, Sequence, Tuple

import jax
import numpy as np

from tree_attention_tpu import obs

# The measurement-hygiene guards (physical ceiling, deflation screen,
# jitter note) file their verdicts here as well as into the records they
# annotate, so a round's runs can be audited for guard-trip rates without
# re-parsing every record (ISSUE 1: deflation/ceiling verdicts as
# structured events).
_GUARD_VERDICTS = obs.counter(
    "timing_guard_verdicts_total",
    "measurement-hygiene guard verdicts by guard kind",
    labels=("record", "guard"),
)


def record_guard_verdict(
    record: str, guard: str, reason: Optional[str] = None
) -> None:
    """Count one guard verdict and mirror it as a trace instant.

    ``guard`` kinds (one physical fault can legitimately file under the
    side the guard actually computed — the label says WHICH screen fired):

    - ``ceiling`` — a derived rate (implied bandwidth, MFU) exceeds the
      hardware spec: the fence did not fence (bench.py's slope records);
    - ``floor`` — a wall-clock reading sits below the physical minimum
      time for the workload (bench_decode's median check, ``tools/tune_sweep.py``'s
      per-cycle screen) — the time-domain dual of ``ceiling``;
    - ``deflation`` — min cycle far below its siblings' median: the
      fence resolved before the chained program finished;
    - ``jitter`` — wide spread / median≫min: contended window, estimate
      stands but is an upper bound;
    - ``clean`` — every screen that ran passed (``reason`` names any
      screen the call site could not run, e.g. jitter needs >= 3 repeats).
    """
    if obs.REGISTRY.enabled:
        _GUARD_VERDICTS.labels(record=record, guard=guard).inc()
    if obs.TRACER.active:
        # Each instrument under its own guard: a tracer-only run used to
        # lose every guard_verdict event to the registry early-return.
        args = {"record": record, "guard": guard}
        if reason:
            args["reason"] = reason
        obs.instant("guard_verdict", cat="timing", args=args)


@dataclasses.dataclass
class TimingStats:
    """Per-call wall-clock stats over ``iters`` fenced repetitions, seconds."""

    median: float
    mean: float
    minimum: float
    maximum: float
    iters: int
    times: Sequence[float]

    def tokens_per_sec(self, tokens: int) -> float:
        return tokens / self.median

    def as_dict(self) -> Dict[str, Any]:
        return {
            "median_s": self.median,
            "mean_s": self.mean,
            "min_s": self.minimum,
            "max_s": self.maximum,
            "iters": self.iters,
        }


def time_fn(
    fn: Callable[..., Any],
    *args: Any,
    iters: int = 10,
    warmup: int = 2,
    fetch: bool = False,
    **kwargs: Any,
) -> TimingStats:
    """Time ``fn(*args, **kwargs)`` with compile warmup and result fencing.

    ``fetch=True`` fences by copying every output to host instead of
    ``block_until_ready``; it adds the device→host transfer to the measured
    time, so pair it with :func:`time_per_step` slope timing to cancel fixed
    overhead.
    """
    if iters < 1:
        raise ValueError(f"iters must be >= 1, got {iters}")

    def fence(res):
        if fetch:
            jax.tree.map(np.asarray, res)
        else:
            jax.block_until_ready(res)

    from tree_attention_tpu.host_runtime import heartbeat

    # One span per time_fn call (never per iteration — the timed loop must
    # not carry telemetry), trace-sinked only when a sink is armed.
    with obs.span("time_fn", cat="timing",
                  args=None if not obs.TRACER.active else
                  {"iters": iters, "warmup": warmup, "fetch": fetch}):
        for _ in range(max(warmup, 0)):
            fence(fn(*args, **kwargs))
            heartbeat()  # each fenced iteration is host-visible progress
        times = []
        for _ in range(iters):
            t0 = time.perf_counter()
            fence(fn(*args, **kwargs))
            times.append(time.perf_counter() - t0)
            heartbeat()
    return TimingStats(
        median=statistics.median(times),
        mean=statistics.fmean(times),
        minimum=min(times),
        maximum=max(times),
        iters=iters,
        times=tuple(times),
    )


@dataclasses.dataclass
class SlopeStats:
    """Per-step slope estimate over ``repeats`` independent measurement
    cycles (each cycle: min-of-``iters`` small chain, min-of-``iters`` large
    chain, slope of the difference).

    ``per_step`` is the minimum over positive cycle slopes — host
    contention is additive, so a cycle whose window hit it only ever
    *inflates* its slope, and the min converges to the true cost.
    ``spread_pct`` ((max−min)/min over the positive slopes) is the run's
    recorded variance: a large spread says some cycles were noisy and the
    min is doing real work (a record must carry its own error bar).
    """

    per_step: float
    slopes: Tuple[float, ...]
    spread_pct: float
    small: TimingStats
    large: TimingStats


DEFLATION_MIN_CYCLES = 3
DEFLATION_RATIO = 0.6


def deflation_suspect(slope: "SlopeStats") -> Optional[str]:
    """Reason string when the min cycle looks DEFLATED, else None.

    The additive-noise model behind the min-stat estimator (contention
    only ever inflates a cycle) fails when a fence resolves before the
    chained program has finished: such a cycle reads too FAST, sometimes
    past the physical roofline (caught by the bandwidth/MFU ceiling
    guards), sometimes not. A deflated cycle shows up as the min sitting
    far below the median of its siblings (< ``DEFLATION_RATIO`` x);
    genuine contention (e.g. cycles of [359, 359, 497] us) keeps
    min ~= median.

    Needs at least ``DEFLATION_MIN_CYCLES`` positive cycles: with two,
    median == mean and the test would flag one ordinarily-contended
    cycle at >2.33x as a deflated min. Callers that want this defence
    must run ``repeats >= 3``.

    Known bound of the defence: a fault window long enough to deflate
    MOST cycles by a similar factor keeps min ~= median and passes this
    screen — by construction no intra-run statistic can separate that
    from a genuinely clean capture. The remaining nets for that case are
    the physical-ceiling guards (a whole-window deflation large enough
    to matter usually crosses the bandwidth/MFU spec) and cross-capture
    comparison: records publish their
    ``slope_cycles_us`` + commit + timestamp precisely so a later reader
    can diff same-shape captures across runs.
    """
    positive = [s for s in slope.slopes if s > 0]
    if len(positive) < len(slope.slopes):
        # A non-positive cycle is hard evidence of a faulty window on its
        # own — a chain cannot cost nothing — regardless of how many
        # clean-looking siblings survive: the surviving min is data from
        # the same window that produced the nonsense cycles. "Could not
        # check" must not read as "checked and clean". (Flagging costs
        # only a re-run.)
        return (
            f"only {len(positive)} of {len(slope.slopes)} cycle slopes "
            "positive: the non-positive cycles signal a faulty measurement "
            "window; discard this record"
        )
    if len(positive) >= DEFLATION_MIN_CYCLES:
        med = statistics.median(positive)
        if slope.per_step < DEFLATION_RATIO * med:
            return (
                f"min cycle {slope.per_step * 1e6:.0f} us is "
                f"<{DEFLATION_RATIO}x the median cycle {med * 1e6:.0f} us: "
                "deflation fault suspected (fence resolved early); "
                "discard this record"
            )
    return None


def slope_per_step(
    make_fn: Callable[[int], Callable[..., Any]],
    *args: Any,
    n_small: int = 64,
    n_large: int = 256,
    iters: int = 5,
    warmup: int = 1,
    fetch: bool = True,
    stat: str = "median",
    repeats: int = 1,
    **kwargs: Any,
) -> SlopeStats:
    """Amortised per-step cost by slope: time an ``n_small``-step and an
    ``n_large``-step chained program and divide the difference.

    Cancels every fixed cost — dispatch and the host fetch used as the
    completion fence — leaving only the marginal cost of one step.
    ``make_fn(n)`` must return a callable running ``n`` dependent steps.

    ``stat`` picks the per-side estimator: ``"median"`` (default) or
    ``"min"``. Where the noise is additive (a shared host's contention),
    the minimum over ``iters`` repetitions converges to the true time; the
    median is the default for backends where run-to-run variance is
    symmetric.

    ``repeats`` runs the whole (small, large) cycle that many times on the
    SAME compiled programs (no recompiles after the first) and takes the
    minimum positive slope — the defence against a single contended
    measurement window inflating both sides' minima together, which one
    cycle cannot detect. The per-cycle slopes and
    their spread come back in :class:`SlopeStats` so records can publish
    their variance.

    Protocol note: have the chain return a small *reduction* of its output
    (e.g. ``out.sum()``), not the full tensor — the fence fetches the result
    to host, and a multi-MB fetch adds transfer time to every call that the
    slope then has to cancel.
    """
    if not 0 < n_small < n_large:
        raise ValueError(f"need 0 < n_small < n_large, got {n_small}, {n_large}")
    if stat not in ("median", "min"):
        raise ValueError(f"stat must be 'median' or 'min', got {stat!r}")
    if repeats < 1:
        raise ValueError(f"repeats must be >= 1, got {repeats}")
    fn_small = make_fn(n_small)
    fn_large = make_fn(n_large)
    pick = (lambda s: s.minimum) if stat == "min" else (lambda s: s.median)
    slopes = []
    s_small = s_large = None
    for cycle in range(repeats):
        # Warmup (the compile) only on the first cycle; later cycles reuse
        # the executables, so extra warmup runs would just spend the
        # machine's time without changing the estimator.
        w = warmup if cycle == 0 else 0
        with obs.span("slope_cycle", cat="timing",
                      args=None if not obs.TRACER.active else
                      {"cycle": cycle, "n_small": n_small,
                       "n_large": n_large}):
            s_small = time_fn(
                fn_small, *args, iters=iters, warmup=w, fetch=fetch, **kwargs
            )
            s_large = time_fn(
                fn_large, *args, iters=iters, warmup=w, fetch=fetch, **kwargs
            )
        slopes.append((pick(s_large) - pick(s_small)) / (n_large - n_small))
    positive = [s for s in slopes if s > 0]
    if not positive:
        raise RuntimeError(
            f"non-positive per-step slope in every cycle ({slopes}): {stat}s "
            f"at n={n_small}/{n_large} — measurement noise exceeds the "
            f"workload; raise n_large or iters"
        )
    spread = (max(positive) - min(positive)) / min(positive) * 100
    return SlopeStats(
        per_step=min(positive),
        slopes=tuple(slopes),
        spread_pct=spread,
        small=s_small,
        large=s_large,
    )


def chain_slope(
    step: Callable[..., Any],
    carry: Any,
    *rest: Any,
    n_small: int,
    n_large: int,
    iters: int = 5,
    warmup: int = 1,
    stat: str = "min",
    repeats: int = 3,
) -> SlopeStats:
    """Slope-time ``step`` via an on-device dependent chain.

    The one harness for per-step kernel timing (bench.py's decode/q8/train
    records and the tile A/B): ``step(carry, *rest) -> next_carry`` is
    chained ``n`` times under ``lax.scan`` (each step consumes the previous
    output, so nothing can overlap or be elided), the chain returns a
    SCALAR reduction of the final carry (so the fence fetches four bytes),
    and the (small, large) chain pair goes through
    :func:`slope_per_step`'s min-stat repeated-cycle protocol. Callers
    that need gradients or multi-output steps fold them into the carry
    themselves — XLA dead-code-eliminates any output that does not feed
    the carry chain.
    """
    import jax.numpy as jnp
    from jax import lax

    def mk(n):
        def f(c, *r):
            def body(cc, _):
                return step(cc, *r).astype(cc.dtype), None

            out = lax.scan(body, c, None, length=n)[0]
            return jnp.sum(out.astype(jnp.float32))

        return jax.jit(f)

    return slope_per_step(
        mk, carry, *rest, n_small=n_small, n_large=n_large,
        iters=iters, warmup=warmup, stat=stat, repeats=repeats,
    )


def time_per_step(
    make_fn: Callable[[int], Callable[..., Any]],
    *args: Any,
    **kwargs: Any,
) -> Tuple[float, TimingStats, TimingStats]:
    """Single-cycle form of :func:`slope_per_step` (kept for callers that
    unpack the original 3-tuple); same parameters and semantics."""
    s = slope_per_step(make_fn, *args, **kwargs)
    return s.per_step, s.small, s.large


def device_memory_stats(device: Optional[jax.Device] = None) -> Optional[Dict[str, int]]:
    """Allocator stats for one device (peak HBM lives in ``peak_bytes_in_use``).

    Returns None on backends without memory stats (e.g. CPU).
    """
    if device is None:
        device = jax.devices()[0]
    try:
        stats = device.memory_stats()
    except Exception:
        return None
    if not stats:
        return None
    return {k: int(v) for k, v in stats.items() if isinstance(v, (int, float))}


@contextlib.contextmanager
def trace(log_dir: Optional[str]) -> Iterator[None]:
    """``jax.profiler`` trace capture; no-op when ``log_dir`` is falsy."""
    if not log_dir:
        yield
        return
    with jax.profiler.trace(log_dir):
        yield
