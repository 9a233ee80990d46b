"""Neutral resource tables for the device scorers, and the check that a
resource kernel fed them agrees with its plain twin.

Shared by ``test_closed_form_kernels.py``, ``test_edit_scoring.py`` and
``test_count_edit_scoring.py``.
"""

import numpy as np

from repro.core import paper_cluster
from repro.core.sim_jax import network_tables


def neutral_tail(n: int, m: int) -> list:
    """``sim_jax.device_resources``' operand tail for n components on m
    machines with no resource in play: no memory demand against unbounded
    memory, and the network tables of a cluster without a distance matrix,
    which hold no component."""
    no_network = paper_cluster((1, 1, 1))
    assert not no_network.has_network
    return [
        np.zeros(n),
        np.full(m, np.inf),
        *network_tables(no_network, None, None, None),
    ]


def assert_edit_parity(got: np.ndarray, want: np.ndarray) -> None:
    """An edit kernel's throughputs on the neutral tail, ``got``, against
    its plain twin's, ``want``, over the same candidate cells: equal to
    1e-12 (the two patch machine sums in different orders), the same
    infeasible (zero) cells, and each kernel's best cell a best of the
    other's."""
    assert np.any(want > 0.0)
    np.testing.assert_allclose(got, want, rtol=1e-12, atol=0.0)
    np.testing.assert_array_equal(got == 0.0, want == 0.0)
    pick, best = int(np.argmax(got)), int(np.argmax(want))
    assert want[pick] == want[best] and got[best] == got[pick]
