"""Per traced collect, mean: seconds on device 0 in the region program
that holds joins (exec/mesh_region.py ``jit_mesh_region_join``: the
pipeline's stages, every absorbed join's probe and gather and the
terminal collective, one launch a region).  3.15 of the mesh cell's 3.79 s
collect until PR 44 (each join ranked stream + build together by a sort
and searched its gather plan); since then each join probes a build
prepared outside the program."""
from benchmark.harness.layer_reads import program_seconds

PROGRAMS = ("jit_mesh_region_join",)


def read(facts):
    return program_seconds(facts, PROGRAMS)
