"""Cells of BENCHMARK.json at sizes a CPU test run can hold: the same plans,
references, limits and harness, with widths, lanes and lengths cut down by
each plan's own ``tiny`` hook."""

from bench import harness


def shrink(cell: harness.Cell) -> harness.Cell:
    """``cell`` at its plan's CPU test sizes."""
    model, traffic = cell.plan.tiny(cell.model, cell.traffic)
    return harness.Cell(cell.bench, cell.name, model=model, traffic=traffic,
                        limits=cell.limits, plan=cell.plan)


def cell(name: str) -> harness.Cell:
    return shrink(harness.Cell(harness.load_json(harness.ROOT / "BENCHMARK.json"), name))


def cells():
    bench = harness.load_json(harness.ROOT / "BENCHMARK.json")
    return [w["name"] for w in bench["workloads"]]
