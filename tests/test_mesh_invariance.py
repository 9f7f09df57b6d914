"""Cross-mesh invariance of the compressed gradient collectives: every peer
holds bitwise-identical synced gradients, equal to the single-device
reference replay (``tests/mesh_sweep.py`` states the contract and the cases).

Each mesh runs in a child process on fake host devices.  The 1- and 2-peer
meshes and the collective-count check are here; the 4- and 8-device meshes
have a test file each, so xdist's ``--dist loadfile`` runs the sweep on
several workers at once.  The long 2-peer sweep comes first: xdist hands a
worker its next file only once fewer than three of its tests are pending,
so nothing queues behind the long case.
"""
import pytest
from mesh_sweep import run_case


@pytest.mark.parametrize("mesh_id", ["data2", "data1"])
def test_sync_matches_reference(mesh_id):
    run_case(mesh_id)


def test_elastic_keeps_collective_counts():
    """The live mask is a replicated in-graph value: enabling elastic must
    not add (or remove) a single traced collective in any sync mode."""
    run_case("counts")
