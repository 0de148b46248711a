"""Online window controller (SERVING.md rung 26).

The overlap pipeline's throughput law (rung 16) is

    steps/s = W / max(R, W * t)

where ``W`` is the dispatched window, ``t`` the per-step device time,
and ``R`` the per-boundary host turnaround (bookkeeping + dispatch +
harvest — everything the device window must hide). The law
saturates once ``W * t >= R`` — beyond that point a larger
window buys no throughput and only adds boundary staleness (cancels,
newcomers, and checkpoints wait up to a full window). The optimal
window is therefore the SMALLEST power of two whose device time covers
the host turnaround.

This module closes the loop on that written-down model using the
rung-25 measurements the serving layer already takes at every harvest:

* ``device_ms``  — the forced device sync inside the harvest
  (``serve_device_ms_window``), giving ``t = device_ms / W``;
* ``rtt_ms``     — dispatch->harvest wall time, whose excess over
  ``device_ms`` is transport + dispatch bookkeeping;
* ``host_ms``    — post-harvest host processing
  (``serve_window_host_ms``).

``R`` is estimated as ``max(rtt_ms - device_ms, 0) + host_ms`` and
both ``R`` and ``t`` are EWMA'd so one slow boundary (a checkpoint, a
GC pause) does not whipsaw the window.

Correctness note: the window is pure SCHEDULING — the greedy argmax
and the positional ``fold_in(seed, t)`` key schedule make emitted
tokens identical for every window size (rung 16 exactness tests).
The controller can therefore never violate bit-identity; it only moves
work between host and device. That is also why the controller lives
OUTSIDE the lock discipline: it is plain-data, owned by the serving
loop, mutated only with the work lock held (like the journal — the
caller's lock, no locks here), and it survives ``revive()`` and slice
reformation because the server never recreates it.
"""

from __future__ import annotations

__all__ = ["pick_window", "WindowController"]


def _pow2_floor(w: int) -> int:
    return 1 if w <= 1 else 1 << (int(w).bit_length() - 1)


def pick_window(r_ms: float, t_ms: float, lo: int, hi: int) -> int:
    """Smallest power-of-two ``W`` in ``[lo, hi]`` with ``W*t >= R``.

    Pure function of the two EWMA'd measurements — the controller law,
    separated out so the convergence tests can drive it against a
    synthetic (R, t) schedule without a server. ``lo``/``hi`` are
    clamped to powers of two (floor), matching the serving layer's
    compiled-program set {1, 2, 4, ...}. Degenerate measurements
    (``t <= 0``: the device looks free) pin to ``hi`` — the largest
    window amortizes an unmeasurably-fast device best.
    """
    lo = _pow2_floor(max(1, int(lo)))
    hi = _pow2_floor(max(1, int(hi)))
    if hi < lo:
        hi = lo
    if t_ms <= 0.0:
        return hi
    w = lo
    while w < hi and w * t_ms < r_ms:
        w <<= 1
    return w


class WindowController:
    """EWMA state + the :func:`pick_window` law for one serving loop.

    All methods are plain-data and called with the serving work lock
    held; the instance itself takes no locks.
    """

    __slots__ = ("lo", "hi", "alpha", "_r", "_t", "_updates")

    def __init__(self, lo: int = 1, hi: int = 256,
                 alpha: float = 0.2):
        if not (0.0 < alpha <= 1.0):
            raise ValueError("alpha must be in (0, 1]")
        self.lo = _pow2_floor(max(1, int(lo)))
        self.hi = _pow2_floor(max(1, int(hi)))
        if self.hi < self.lo:
            raise ValueError("window bounds inverted: "
                             f"[{lo}, {hi}]")
        self.alpha = float(alpha)
        self._r = 0.0
        self._t = 0.0
        self._updates = 0

    def observe(self, *, rtt_ms: float, device_ms: float,
                host_ms: float, window: int) -> None:
        """Feed one harvested window's measurements (lock held).

        ``window`` is the size that was actually dispatched — the
        per-step device time is ``device_ms / window``. The first
        observation seeds the EWMAs directly (no warm-up bias toward
        zero)."""
        if window <= 0:
            return
        r = max(float(rtt_ms) - float(device_ms), 0.0) + float(host_ms)
        t = max(float(device_ms), 0.0) / float(window)
        a = self.alpha
        if self._updates:
            self._r += a * (r - self._r)
            self._t += a * (t - self._t)
        else:
            self._r = r
            self._t = t
        self._updates += 1

    def window(self, default: int | None = None) -> int:
        """Current recommendation: :func:`pick_window` on the EWMAs.
        Before the first observation returns ``default`` (clamped) —
        the operator's static seed — or ``hi`` when none given."""
        if not self._updates:
            if default is None:
                return self.hi
            return max(self.lo, min(self.hi,
                                    _pow2_floor(max(1, default))))
        return pick_window(self._r, self._t, self.lo, self.hi)

    def snapshot(self) -> dict:
        """Plain-dict state for /status + the flight recorder."""
        return {
            "window": self.window(),
            "r_ms": self._r,
            "t_ms": self._t,
            "updates": self._updates,
            "lo": self.lo,
            "hi": self.hi,
        }
