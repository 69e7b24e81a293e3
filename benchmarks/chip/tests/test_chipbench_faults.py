"""With the control or a fault planted under the timed path, the check
comes out false: the control (8-bit integer arithmetic in the GF(256)
kernel's place), an answer altered where it is produced, half of the batch
left out, a step that leaves the store's state unchanged, and (where files
are edited) a block diff that leaves a changed block unwritten. No cell
has an exchange between chips, so that fault does not apply."""
import pytest

from _tiny import run

CASES = [
    ("emulab_k6.ingest", "int8"), ("emulab_k6.ingest", "flip"),
    ("emulab_k6.ingest", "half"), ("emulab_k6.ingest", "unstored"),
    ("aws_k2.ingest", "int8"),
    ("emulab_k6.degraded_read", "int8"), ("emulab_k6.degraded_read", "flip"),
    ("emulab_k6.degraded_read", "half"),
    ("emulab_k6.edit", "int8"), ("emulab_k6.edit", "flip"), ("emulab_k6.edit", "half"),
    ("emulab_k6.edit", "unstored"), ("emulab_k6.edit", "stale"),
]


@pytest.mark.parametrize("cell,fault", CASES)
def test_the_check_catches_it(cell, fault, monkeypatch):
    from chipbench import faults

    with faults.planted(fault):
        line = run(cell, monkeypatch)
    assert not line["correct"]
    assert any(c["value"] > c["limit"] for c in line["checks"].values())
