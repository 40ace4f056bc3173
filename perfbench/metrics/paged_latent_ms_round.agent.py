"""Kernels: ``paged_latent_ms_round.longgen``'s reading (device milliseconds
per decode round in ``paged_decode_latent*``) for the agent cell, where the
kernel runs twice a layer (8 planes) at 64 heads. 0 when no such kernel or
no round is in the window."""
from perfbench import manifest

read = manifest.load_module("metrics", "paged_latent_ms_round.longgen").read
