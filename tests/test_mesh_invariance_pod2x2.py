"""Cross-mesh invariance sweep on the 2-pod x 2-data mesh (see ``tests/mesh_sweep.py``);
a file of its own so xdist's ``--dist loadfile`` gives it its own worker."""
import pytest
from mesh_sweep import run_case


@pytest.mark.parametrize("mesh_id", ["pod2x2"])
def test_sync_matches_reference(mesh_id):
    run_case(mesh_id)
