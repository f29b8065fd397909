"""The test cells: the dense family at toy widths on the CPU, driven by
the same harness as the chip cells (bench/tests/data)."""

from pathlib import Path

from bench import run
from bench.spec import Spec

DATA = Path(__file__).resolve().parent / "data"


def spec() -> Spec:
    return Spec.load(DATA / "BENCHMARK.json", traffic_dir=DATA / "traffic",
                     checks_dir=DATA / "checks")


def run_cell(name: str, seed: int, seconds: float = 2.0,
             traced: bool = False, control: bool = False) -> dict:
    return run.run_cell(spec(), name, seed, seconds, traced,
                        require_chip=False, control=control)
