"""The ``stale_matrix`` control, inside the server's process.

``control_fresh.py`` puts this directory on the server's ``PYTHONPATH``
(through the configuration's ``server_env``), so Python imports this file
when the server starts: the program then keeps the decode matrix of the
first rebuild it runs and decodes every later rebuild with it, whatever
shards that one has lost. What a cache of decode matrices (or of their
device copies) keyed by too little would do.
"""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[3]))

from seaweedfs_tpu.ops import rs_jax  # noqa: E402

_real = rs_jax.Encoder.decode_matrix
_kept: dict = {}


def _stale(self, present, wanted=None):
    return _kept.setdefault("first", _real(self, present, wanted))


rs_jax.Encoder.decode_matrix = _stale
