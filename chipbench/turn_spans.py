"""What the readers of an admission share.  Beside a tick and a batch the
program's lane log (``lane_spans.records``) keeps a ``slot.turn`` record an
adopted hand-off: the slot's release (``t_free``; ``None`` for a slot that held
nothing before), the batch that prefilled the next request into it (``t_start``
to ``t_first``), the hand-off (``t_handoff``, ``t_adopt``) and the adopting
tick's tokens (``t_tok``), all on the clock of ``obs["t0_abs"]``.  A tick says
which prefill batches were on the device's queue ahead of its step (``behind``), a
batch how many slots stood free when it was taken (``free_slots``).  A program
without the kind or a field gives every reader here nothing, and it returns
``None``.
"""
from __future__ import annotations

import statistics

import lane_spans

#: ticks of a kind under which a median of them says nothing
MIN_TICKS = 5


def ending_in_window(obs, kind, stamp, field=None):
    """The window's records of ``kind``: a record counts where it ends, so those
    whose ``stamp`` lies inside the window (and that carry ``field``)."""
    lo = obs["t0_abs"]
    hi = lo + obs["window_s"]
    return [r for r in lane_spans.records(obs, kind)
            if lo <= r[stamp] < hi and (field is None or field in r)]


def turns(obs, released=False):
    """The turns whose adopting tick's tokens reached the host inside the window.
    ``released``: those only whose slot was also released inside it: not the
    ramp's (the slot held nothing) nor a slot that the warm-up left free."""
    out = ending_in_window(obs, "slot.turn", "t_tok")
    if released:
        out = [t for t in out
               if t["t_free"] is not None and t["t_free"] >= obs["t0_abs"]]
    return out


def median_ms(recs, first, last):
    """Median over ``recs`` of ``last`` - ``first`` in milliseconds."""
    if not recs:
        return None
    return statistics.median((r[last] - r[first]) * 1e3 for r in recs)


def ticks_by_behind(obs):
    """-> (the window's ticks dispatched with a prefill batch on the device, those
    with none); ``None`` where the tick records do not say."""
    ticks = ending_in_window(obs, "decode.tick", "t_tok", field="behind")
    if not ticks:
        return None
    return ([t for t in ticks if t["behind"]],
            [t for t in ticks if not t["behind"]])
