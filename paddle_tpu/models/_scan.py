"""Shared helpers for the stacked-block ``lax.scan`` model skeleton."""

from __future__ import annotations


def resolve_scan_unroll(config) -> int:
    """Layers per scan step.  1 = rolled loop (O(1) compile in depth);
    num_layers = fully unrolled (no dynamic_slice/update HBM traffic; a
    builder's round-2 profile on one v5e put that traffic at ~11 ms/step at
    the gpt2s bench shapes — not measured since)."""
    return max(1, int(getattr(config, "scan_unroll", 1) or 1))
