"""Runner `serve_closed_loop`: callers that each wait for a reply. Every
client sends its next request when the previous one has finished; a request
is due when it is sent. The clients run through a lead-in before the window
opens."""

from __future__ import annotations

from typing import Any, Dict

from ..lib import serve_driver
from ..lib.spec import Cell


def run(cell: Cell) -> Dict[str, Any]:
    tr = cell.traffic
    horizon = float(tr["lead_in_s"]) + cell.seconds + (float(tr["trace_seconds"]) if cell.trace else 0.0)
    return serve_driver.run_cell(cell, lambda load, t_start: load.start_closed(), horizon)
