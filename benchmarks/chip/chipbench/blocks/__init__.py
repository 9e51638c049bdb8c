"""Model blocks, one module each, found by the name a configuration file
gives under `"block"` (`chipbench.spec.load_block`).  What a block defines
is listed in `chipbench/spec.py` (`BLOCK_FUNCTIONS`)."""
