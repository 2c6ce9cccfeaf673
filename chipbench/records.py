"""What one run recorded, as the metric readers see it.

Each metric of ``BENCHMARK.json`` has a reader ``metrics/<name>.py`` with
one function ``read(run) -> float | None`` over a :class:`Run`.  A reader
that finds nothing to read returns None, and the metric is left out of
the run's result line.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import List, Optional

import numpy as np

from .serving import Request, Window


@dataclass
class Run:
    seconds: float              # the window's length as asked
    setup_s: float
    window: Window
    recall: Optional[np.ndarray] = None   # per answered request
    trace: Optional[dict] = None          # tracing.reduce_trace's summary

    @property
    def requests(self) -> List[Request]:
        return self.window.requests

    def answered(self) -> List[Request]:
        return [r for r in self.requests if r.done is not None and not r.shed]
