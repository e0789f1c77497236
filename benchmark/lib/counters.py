"""Readers of the engine's `n.<name>` counters (benchmark/lib/spans.count)
as the forms the per-layer metrics give them: per kernel launch, per pack
that a launch followed, and one counter over another. None wherever a
counter was not recorded: a program older than the counter, a cell
without such traffic, or a run below full sampling."""
from __future__ import annotations

from benchmark.lib import spans


def per_launch(run, name: str):
    n = spans.count(run, name)
    launches = run.window["launches"]
    return n / launches if n is not None and launches else None


def ratio(run, over: str, under: str):
    a, b = spans.count(run, over), spans.count(run, under)
    return a / b if a is not None and b else None


def per_pack(run, name: str):
    """What `_pack` counted, over the packs it counted it in (`n.packs`:
    one a launch). The window's edges fall between a pack and its launch,
    so the window's own launches are one pack off, 5 % of a window of 20."""
    return ratio(run, name, "packs")
