"""Small CPU versions of the benchmark's cells, for the tests."""

from pathlib import Path

from bench import harness

ROOT = Path(__file__).resolve().parents[2]


def tiny_cell(name: str, window: int = 3000):
    """The cell as committed, cut to a window the CPU runs in seconds."""
    cell = harness.load_cell(ROOT, name)
    cell.config = dict(cell.config, window=window)
    cell.traffic = dict(cell.traffic, warm_steps=4, fill_request=1024)
    if cell.traffic.get("queries"):
        cell.traffic["queries"] = dict(cell.traffic["queries"], rate_per_s=20, max_rows=64,
                                       pool_rows=4096)
        cell.traffic["writer"] = dict(cell.traffic["writer"], ops_per_s=4000)
    return cell


def drive(cell, seed: int, seconds: float = 1.5, patch=None):
    """Set up, optionally break the engine (``patch(engine)``), run the
    window and return (program outputs, expected)."""
    h = harness.Harness(cell, seed)
    h.setup()
    if patch is not None:
        patch(h.eng)
    h.window(seconds, harness.CompileMeter())
    return h.outputs(), h.expected()
