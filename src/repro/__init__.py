"""Torrent distributed-DMA reproduction (jax)."""
