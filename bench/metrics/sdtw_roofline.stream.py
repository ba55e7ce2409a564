"""Share of the roofline that the sDTW Pallas kernel reached in the
monitoring cells, in %: ``sdtw_roofline``'s reader, unchanged, for the
cells whose end-to-end metric is a latency. There the calls are the
window's ticks (every stream's templates against the tick's true
samples, nominal cells)."""
from pathlib import Path

from bench.run import load_module

read = load_module(Path(__file__).with_name("sdtw_roofline.py")).read
