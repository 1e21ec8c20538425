"""The engine-level cases (tests/_block_cases.py) on ONE row of
tests/_blocks.py:BLOCKS: a file a block, so that the suite's workers share
the blocks."""

from _block_cases import *  # noqa: F401,F403

BLOCK = "ouro"
