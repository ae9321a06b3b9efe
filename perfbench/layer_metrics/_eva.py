"""What the ``eva_*`` readers share: the ``serving.decode`` spans that carry
the rows ONE EVA layer attends over in a tick (``eva_window_rows``,
``eva_summary_rows``) and the positions its lanes stand at
(``eva_positions``: what full attention would read), as
``fleetx_tpu/serving/engine.py`` sets them over EVA's two classes of page.
Empty for a program that has no such span field (a parent commit's, another
configuration's)."""

from __future__ import annotations

FIELDS = ("eva_window_rows", "eva_summary_rows", "eva_positions")


def decode_rows(run, inside=None) -> list:
    """``(window rows, summary rows, positions)`` of every decode tick that
    began inside the stretch ``inside`` (default: the measured window)."""
    a, b = inside or run.window
    return [tuple(s.attrs[f] for f in FIELDS)
            for s in run.spans_named("serving.decode")
            if FIELDS[0] in s.attrs and a <= s.start_s <= b]
