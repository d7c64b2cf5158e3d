"""Pallas TPU kernels: compiled on a TPU, interpreted on the CPU (see
platform.py)."""
