"""Rows of kind ``images``: ``{"kind": "images", "image_size",
"num_classes"}``. A kind of row that ``chipbench/`` does not have, added
as a file: the driver finds it by name as it finds ``rows/tokens.py``.

The arithmetic is copied from the program's ``SyntheticImageDataset``
(``data/datasets.py``): normal noise drawn per row over a per-class
low-resolution pattern. At 224 x 224 that is 5 ms a row on the host, which
left ResNet-50 at batch 256 host-bound on the chip (PERF.md Findings PR 25):
a cell at that size needs cheaper rows than these.
"""

from __future__ import annotations

import numpy as np


class Rows:
    def __init__(self, data: dict, seed: int, length: int):
        self.data, self.seed, self.length = data, seed, length
        size = data["image_size"]
        self._pat = min(8, size)
        if size % self._pat:
            raise ValueError("image_size must be a multiple of 8")
        self._patterns = np.random.default_rng((seed, 12345)).standard_normal(
            (min(data["num_classes"], 1024), self._pat, self._pat, 3)
        ).astype(np.float32)

    def __len__(self):
        return self.length

    def __getitem__(self, i: int):
        size = self.data["image_size"]
        label = np.int32(i % self.data["num_classes"])
        img = np.random.default_rng((self.seed, i)).standard_normal(
            (size, size, 3), np.float32)
        pat = self._patterns[label % len(self._patterns)]
        rep = size // self._pat
        pat = np.repeat(np.repeat(pat, rep, 0), rep, 1)
        return {"image": (0.7 * img + 0.7 * pat).astype(np.float32),
                "label": label}
