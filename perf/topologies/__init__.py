"""Copies of the deployments' topology generators.

Frozen here so that a change to the program (openr_tpu/utils/topo.py)
cannot move a benchmark deployment.  Each generator returns plain data
(`perf.deployment.Topology`); it imports nothing of the program.
"""
