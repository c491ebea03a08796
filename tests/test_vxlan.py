"""Paper Section II: VXLAN encapsulation & ECMP hash entropy.

The paper argues encapsulation limits the fields available to transit
switches and makes collisions more likely.  Quantified on the testbed:
an RFC-compliant VTEP (outer UDP sport = folded inner-header hash)
preserves nearly all entropy, while hashing on the outer IP pair alone
(broken/legacy VTEP) roughly doubles the imbalance.

Runs on the vectorized engine (bit-identical to the hop-by-hop tracer —
see test_vector_sim.py), so the seed sweep is 8 seeds instead of 4 at a
fraction of the cost.
"""

import numpy as np

from repro.core import (
    FIELDS_5TUPLE, FIELDS_IP_PAIR, FIELDS_VXLAN, SimSpec, monte_carlo_fim,
)


def _mean_fim(compiled, flows, mode, seeds=8):
    mc = monte_carlo_fim(compiled, flows, np.arange(seeds),
                         spec=SimSpec(fields=mode))
    return float(mc.aggregate.mean())


def test_vxlan_sport_preserves_entropy(paper_compiled, paper_setup):
    _, _, flows = paper_setup
    five = _mean_fim(paper_compiled, flows, FIELDS_5TUPLE)
    vxlan = _mean_fim(paper_compiled, flows, FIELDS_VXLAN)
    assert abs(five - vxlan) < 10.0, (five, vxlan)


def test_ip_pair_hashing_collapses_entropy(paper_compiled, paper_setup):
    """16 NIC-pair combinations per server pair -> far fewer distinct
    hash inputs -> much worse imbalance (paper Section II)."""
    _, _, flows = paper_setup
    five = _mean_fim(paper_compiled, flows, FIELDS_5TUPLE)
    ip_pair = _mean_fim(paper_compiled, flows, FIELDS_IP_PAIR)
    assert ip_pair > five * 1.5, (five, ip_pair)
