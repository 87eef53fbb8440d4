"""The tiny granite cell's block is the benchmark's granitemoehybrid
block."""

from benchmarks.chip.blocks.granitemoehybrid import *  # noqa: F401,F403
