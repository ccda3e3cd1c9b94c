"""Runner `serve_open_loop`: independent users. Requests are sent on the
schedule of the traffic file whether or not earlier ones have finished, and
each is timed from when it was DUE, so a stall is charged to every request
it delays. The schedule runs through a lead-in before the window opens."""

from __future__ import annotations

from typing import Any, Dict

from ..lib import serve_driver
from ..lib.spec import Cell


def run(cell: Cell) -> Dict[str, Any]:
    tr = cell.traffic
    horizon = float(tr["lead_in_s"]) + cell.seconds + (float(tr["trace_seconds"]) if cell.trace else 0.0)
    return serve_driver.run_cell(cell, lambda load, t_start: load.start_open(t_start), horizon)
