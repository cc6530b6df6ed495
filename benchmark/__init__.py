"""The repo's yardstick: one cell, one run, one result line (see README.md).

Nothing in this package imports jax: the chip belongs to the one `server`
child a run starts.
"""
