"""Drive a whole benchmark run on the CPU at a tiny size, past the look for
a chip, with an optional fault planted in the engine; return the result
line as a dict."""

from __future__ import annotations

import contextlib
import io
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
for p in (str(BENCH.parent / "src"), str(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)

import run  # noqa: E402


def drive(workload: str, seed: int, *, rows: int = 256, seconds: float = 1.0,
          extra=(), plant=None) -> dict:
    """``plant(engine)`` may break the engine once it is built."""
    import jax
    args, spec, cell, cfg, mix = run.parse(
        ["--workload", workload, "--seed", str(seed), "--seconds",
         str(seconds), "--scale-rows", str(rows), *extra])

    class Planted(run.Run):
        def build(self):
            super().build()
            if plant is not None:
                plant(self.engine)

    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        code = Planted(args, spec, cell, cfg, mix, jax.devices()).go()
    assert code == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])
