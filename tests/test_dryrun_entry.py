"""Driver entry contract: dryrun_multichip at 16 virtual devices.

VERDICT r3 next #6: the 5 mesh axes were never exercised JOINTLY — the
8-device dryrun runs data x sharding x model and pipe x model x sep as
two separate configs. At 16+ devices dryrun_multichip adds config C: ONE
mesh with data x sharding x pipe x model all >1 (x sep at 32), composing
ZeRO-2 slot sharding + the 1F1B schedule + Megatron TP (+ ring-attention
SP) jointly with loss parity against a single device — the composition
the north-star config actually stacks (fleet/base/topology.py 4-D
topology).

dryrun_multichip re-execs itself in a subprocess with the right virtual
device count, so this runs under the 8-device conftest unchanged.
"""
import os
import sys

import pytest


@pytest.mark.timeout(900)
def test_dryrun_multichip_16_joint_axes():
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    import __graft_entry__ as g

    g.dryrun_multichip(16)  # raises on any parity failure
