"""Least bytes the fleet's whole-step kernel (`kernels/fleet_step.py`) must
move for one unit of work, derived from the work's shapes alone, for its
roofline share.  The count does not depend on how the program splits the
work into calls: merging or splitting calls leaves it unchanged.

A flush of a fleet advances ``n_steps`` steps of ``n_packages`` packages
of ``n_tiles`` tiles.  The least it can move through HBM, in f32 words:

  * the density chunk, read once:            n_steps * n_packages * n_tiles
  * the scheduler state, read once and written once (2x), per package:
      filtration ring                         window * n_tiles
      pole-bank states                        n_poles * n_tiles
      frequency                               n_tiles
      event counter                           1
      reactive hysteresis latch (if any)      n_tiles
      degraded-fallback plane (if any)        n_tiles + 2  (last rho, stale,
                                                            degraded)
  * per-package inputs that are only read:
      operator mode pin (mixed fleets)        1

An experiment of the §10 Monte Carlo runs ``n_trials`` one-tile trials for
``n_steps`` steps under two controllers (the reactive baseline and V24),
each over the trial's own density, as the plain reference
(`bench/reference/montecarlo.py`) defines it:

  * density, read once by each controller:   2 * n_trials * n_steps
  * state, read once and written once (2x), per trial:
      reactive: pole state, frequency, latch  3
      V24: pole state and filtration ring     1 + window
  * per-trial physics, only read:             decay, gain, polling period

Counted at the real tile, package and trial counts: no sublane or 128-lane
padding.  Not counted: the per-step temperature and frequency traces the
kernel streams out today, which are the engine's intermediates (a change that
stops writing them must not read over 100 %), and the ring's sliding sums,
which the ring determines.  The kernel's arithmetic is f32 VPU work, for
which no published peak exists, so only the HBM bound applies.
"""
from __future__ import annotations

WORD = 4


def fleet_step_bytes(n_steps: int, n_packages: int, n_tiles: int,
                     window: int, n_poles: int, *, latch: bool = False,
                     fallback: bool = False, mixed: bool = False) -> int:
    """Least bytes of one flush of ``n_steps`` steps of a fleet."""
    state = (window + n_poles + 1) * n_tiles + 1
    if latch:
        state += n_tiles
    if fallback:
        state += n_tiles + 2
    read_only = 1 if mixed else 0
    words = (n_steps * n_packages * n_tiles
             + n_packages * (2 * state + read_only))
    return WORD * words


def flush_bytes(sched: dict, n_steps: int, n_packages: int) -> int:
    """`fleet_step_bytes` of one flush of a fleet with the scheduler fields
    ``sched`` (a configuration file's ``scheduler``)."""
    return fleet_step_bytes(
        n_steps, n_packages, sched["n_tiles"], sched["filtration_window"],
        2 if sched["two_pole"] else 1,
        latch=sched["mixed_mode"] or sched["degraded_fallback"],
        fallback=sched["degraded_fallback"], mixed=sched["mixed_mode"])


def experiment_bytes(n_trials: int, n_steps: int, window: int) -> int:
    """Least bytes of one paired Monte Carlo experiment."""
    state = 3 + (1 + window)
    words = n_trials * (2 * n_steps + 2 * state + 3)
    return WORD * words
