"""Serving substrate: the streaming-PCA fleet engine, with continuous
batching over a fixed device batch."""
