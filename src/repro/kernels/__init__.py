"""Pallas TPU kernels for the residue datapath (each with ops.py + ref.py)."""
