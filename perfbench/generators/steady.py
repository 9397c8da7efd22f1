"""Training traffic: one fixed batch from the seed, stepped back to back.

The batch's shape belongs to the configuration (rows per replica,
sequence length, share of positions that carry a masked-LM label); the
traffic file only says that the same batch repeats for the whole window.
"""

from __future__ import annotations

from typing import Any, Dict

import numpy as np


def make(params: Dict[str, Any], seed: int, rows: int, seq: int,
         vocab_size: int, mlm_label_share: float = 0.0
         ) -> Dict[str, np.ndarray]:
    if not params.get("repeat_one_batch", False):
        raise SystemExit("perfbench: steady traffic repeats one batch")
    rng = np.random.default_rng(int(seed))
    ids = rng.integers(0, vocab_size, size=(rows, seq), dtype=np.int32)
    batch = {"input_ids": ids}
    if mlm_label_share > 0:
        labels = np.full((rows, seq), -100, np.int32)
        masked = rng.random((rows, seq)) < mlm_label_share
        labels[masked] = ids[masked]
        batch["labels"] = labels
    return batch
