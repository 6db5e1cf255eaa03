"""The ``train.program_reuse`` reader on hand-made spans: the share of
``compile`` spans that hold a ``compile.reuse`` child, and None where
the window holds no ``compile`` span."""
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness  # noqa: E402


def _ctx(*spans):
    return SimpleNamespace(spans=list(spans))


@pytest.mark.parametrize("spans, want", [
    # Every call compiles (a program without the cache): 0.
    ([("compile", 0.0, 4.0), ("compile.lower", 0.0, 1.0),
      ("compile", 10.0, 3.0), ("compile.lower", 10.0, 1.5)], 0.0),
    # Every call reuses: 100.
    ([("compile", 0.0, 0.001), ("compile.reuse", 0.0, 0.0005),
      ("compile", 1.0, 0.001), ("compile.reuse", 1.0, 0.0005)], 100.0),
    # One of two, with a nested compile span counted once.
    ([("compile", 0.0, 4.0), ("compile", 0.5, 1.0),
      ("compile.lower", 0.0, 1.0),
      ("compile", 10.0, 0.001), ("compile.reuse", 10.0, 0.0005)], 50.0),
    # No call in the window: nothing to read.
    ([("execute", 0.0, 1.0)], None),
])
def test_program_reuse_share(spans, want):
    got = harness.load_reader("train.program_reuse")(_ctx(*spans))
    assert got == (pytest.approx(want) if want is not None else None)
