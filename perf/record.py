"""The run's collected record, as the metric readers see it, and the few
reductions more than one reader needs.

A reader is ``perf/end_to_end/<name>.py`` or ``perf/layer_metrics/<name>.py``:
``read(record) -> float | None`` plus ``UNIT`` (and, for a layer metric,
``LAYER`` and ``MOVES``, which ``BENCHMARK.json`` repeats). A reader that
finds nothing to read returns None and the metric is left out of the line.
"""

from __future__ import annotations

import dataclasses
import importlib
from typing import List, Optional

import numpy as np


@dataclasses.dataclass
class Record:
    config: dict  # the configuration file, with "name"
    t_process: float  # perf_counter at the start of the process
    t0: float  # perf_counter at the start of the window
    seconds: float
    t_drained: float
    sessions: list  # loadgen.SessionRecord
    children: List[dict]  # one dump per server child
    peaks: Optional[dict] = None

    @property
    def t_end(self) -> float:
        return self.t0 + self.seconds

    def counted(self) -> list:
        return [s for s in self.sessions if s.counted]

    def in_window(self, t: float) -> bool:
        return self.t0 <= t <= self.t_end

    def gap_samples(self) -> np.ndarray:
        """Rows (reply time, ms since the session's reply before it), for
        every decode reply that came inside the window (any session's)."""
        out = []
        for s in self.sessions:
            if s.first_reply is None:
                continue
            last = s.first_reply
            for t, _pos in s.replies:
                if self.in_window(t):
                    out.append((t, (t - last) * 1e3))
                last = t
        return np.asarray(out, float).reshape(-1, 2)

    def gaps_ms(self) -> np.ndarray:
        """Reply-to-reply times of consecutive steps of one session."""
        return self.gap_samples()[:, 1]

    def hop_steps(self, kind: str) -> np.ndarray:
        """Rows (n_hops, network, queue, compute, serialize, other) in
        seconds, one per traced step of that kind ("prefill" | "decode")."""
        rows = [h[1:] for s in self.sessions for h in s.hops if h[0] == kind]
        return np.asarray(rows, float).reshape(-1, 6)

    def hop_part_ms(self, kind: str, column: int) -> np.ndarray:
        """One part of each traced step of that kind, per hop, in ms."""
        steps = self.hop_steps(kind)
        return steps[:, column] / steps[:, 0] * 1e3

    def stat_delta(self, child: dict, key: str, start: str = "window", end: str = "window_end") -> Optional[float]:
        marks = child.get("marks", {})
        if start not in marks or end not in marks:
            return None
        return marks[end]["stats"][key] - marks[start]["stats"][key]

    def ratio_over_children(self, num: str, den: str, **kw) -> Optional[float]:
        n = d = 0.0
        for child in self.children:
            a, b = self.stat_delta(child, num, **kw), self.stat_delta(child, den, **kw)
            if a is None or b is None:
                return None
            n, d = n + a, d + b
        return n / d if d > 0 else None


def percentile(values, q: float) -> Optional[float]:
    values = np.asarray(values, float)
    return float(np.percentile(values, q)) if values.size else None


def load_reader(kind: str, name: str):
    """``kind`` is ``end_to_end`` or ``layer_metrics``."""
    return importlib.import_module(f"perf.{kind}.{name}")
