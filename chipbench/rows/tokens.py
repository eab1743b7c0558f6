"""Rows of kind ``tokens``: ``{"kind": "tokens", "seq_len", "vocab_size"}``.

The training rows a cell is fed are made from the seed by the benchmark. The
driver finds this file by the ``kind`` of the traffic file's ``data`` group
(``rows/<kind>.py``, class ``Rows``) and puts the object in place of the
program's own synthetic dataset; the program loads, collates, shards and
prefetches it as it would any other. Its sampler's order stays as it is, so
every seed gives the same sizes and a different content.

The arithmetic is copied from the program's ``SyntheticTokenDataset``
(``data/datasets.py``) so that a row costs the host what it cost there:
uniform tokens, the targets shifted by one.
"""

from __future__ import annotations

import numpy as np


class Rows:
    def __init__(self, data: dict, seed: int, length: int):
        self.data, self.seed, self.length = data, seed, length

    def __len__(self):
        return self.length

    def __getitem__(self, i: int):
        rng = np.random.default_rng((self.seed, i))
        toks = rng.integers(0, self.data["vocab_size"],
                            self.data["seq_len"] + 1, dtype=np.int32)
        return {"tokens": toks[:-1], "targets": toks[1:]}
