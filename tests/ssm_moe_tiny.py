"""What the files of ``tiny-ssm-moe``'s tests share (tests/test_ssm_moe.py, the
programs; tests/test_ssm_moe_engine.py; tests/test_ssm_moe_cell.py): the
sizes, the tolerance and the helpers that more than one of them calls.
"""

from __future__ import annotations

import numpy as np


# float32 program against the float32 reference at `highest`: sums taken in
# another order (a chunked scan, a grouped product over sorted rows) differ
# in the last places of a float32.
ATOL = 2e-4


def _prompt(seed, n):
    """Token ids under 250: the engine's default tokenizer has 259."""
    return list(np.random.RandomState(seed).randint(1, 250, size=n))


#: Decode's state update as ``ssm.ssm_step`` in XLA, and as the kernel over
#: the live rows (interpreted; the decode program's attention and grouped
#: products are their kernels then too): ISSUE 45.
UPDATES = {"elementwise": {}, "kernel": {"flash_interpret": True}}
