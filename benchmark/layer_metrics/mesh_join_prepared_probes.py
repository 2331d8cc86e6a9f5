"""Per collect, mean over the window: joins inside mesh regions whose
body probed a build PREPARED outside the program by the one-chip
executor's code (``mesh_join.probe.direct`` by address in a table +
``mesh_join.probe.search`` in the sorted keys; exec/mesh_region.py
``_launch``, PR 44).  4 a collect in the mesh cell (q6's four
surrogate-key joins, all dense); 0 from an engine that counts its mesh
joins (``mesh_join_replicated`` / ``mesh_join_partitioned``) but no probe
kind -- every region join then ranks stream + build together by a sort
inside the program, as before PR 44, or takes the sort path still
(``mesh_join.probe.sorted``: a string key); None where no mesh join ran."""
from benchmark.harness.engine_record import window_records

PREPARED = ("mesh_join.probe.direct", "mesh_join.probe.search")
#: an engine counts every mesh join's build mode under one of these
MESH_JOINS = ("mesh_join_replicated", "mesh_join_partitioned")


def read(facts):
    found = window_records(facts)
    records = [] if found is None else found[0] + found[1]
    if not any(k in c for c in records for k in MESH_JOINS):
        return None
    return sum(c.get(k, 0) for c in records for k in PREPARED) / len(records)
