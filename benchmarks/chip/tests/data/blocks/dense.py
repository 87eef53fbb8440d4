"""The tiny cell's block is the benchmark's dense block."""

from benchmarks.chip.blocks.dense import *  # noqa: F401,F403
