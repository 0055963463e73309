"""Photonic-SRAM in-memory-computing reproduction (jax)."""
