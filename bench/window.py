"""What one measured window holds, and the arithmetic over it that readers
share.

The window opens at the delivery of the ramp's last chunk (``t_open``) and
closes at the first delivery at or after ``t_open + seconds`` (``t_close``),
so it holds whole chunks: all the work and all the time between two
deliveries.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import numpy as np

from bench.tap import Chunk, Span, Tap


@dataclass
class Window:
    cell: object                  # spec.Cell
    tap: Tap
    t_open: float
    t_close: float
    setup_s: float
    peaks: Dict                   # bench/peaks.py entry ({} off the chip)
    prompt_len: Dict[int, int]
    compiles: int = 0
    trace: Optional[list] = None          # devtrace.Event list
    trace_window_s: float = 0.0

    @property
    def config(self) -> Dict:
        return self.cell.config

    @property
    def chips(self) -> int:
        """Chips the cell runs on; ``peaks`` are one chip's."""
        return self.cell.chips

    @property
    def counts(self):
        """The configuration's counts of FLOPs and bytes (``spec.Cell``)."""
        return self.cell.counts

    @property
    def slots(self) -> int:
        return int(self.cell.sizes["slots"])

    @property
    def seconds(self) -> float:
        return self.t_close - self.t_open

    def inside(self, t: float) -> bool:
        return self.t_open < t <= self.t_close

    def chunks(self) -> List[Chunk]:
        """Chunks delivered inside the window."""
        return [c for c in self.tap.chunks if c.end and self.inside(c.end)]

    def admits(self) -> List[Span]:
        return [s for s in self.tap.admits if self.inside(s.end)]

    def tokens(self) -> int:
        return sum(c.tokens for c in self.chunks())

    def gaps(self) -> np.ndarray:
        """Time since the same request's previous token, for every token
        delivered in the window (0 after the first of a chunk's tokens; a
        request's first token has no gap)."""
        last: Dict[int, float] = {}
        out: List[float] = []
        for c in self.tap.chunks:
            if not c.end:
                continue
            for rid, k, _, _ in c.slots:
                if self.inside(c.end):
                    if rid in last:
                        out.append(c.end - last[rid])
                        out.extend([0.0] * (k - 1))
                    else:
                        out.extend([0.0] * (k - 1))
                last[rid] = c.end
        return np.asarray(out)

    def served_requests(self) -> List[int]:
        """Requests delivered a token inside the window."""
        return sorted({rid for c in self.chunks() for rid, _, _, _ in
                       c.slots})

    def admitted_prompts(self) -> List[int]:
        """Prompt lengths of the requests admitted in the window."""
        out = []
        for s in self.admits():
            out.extend(self.prompt_len[r] for r in s.args.get("rids", ()))
        return out

    def decode_contexts(self) -> List[Tuple[int, int, int]]:
        """(kv tokens, act tokens, steps) of every slot's part in every
        chunk of the window, the counts before the chunk."""
        return [(kv, act, k) for c in self.chunks() for _, k, kv, act in
                c.slots]
