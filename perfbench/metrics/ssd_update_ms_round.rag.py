"""Kernels: ``ssm_update_ms_round.chat``'s reading (device milliseconds per
decode round in the state's decode kernel, ``tpu_custom_call`` events whose
name holds ``ssm_update``) for the rag cell, where the kernel advances nine
Mamba-2 layers' ``[128, 8192]`` float32 planes a running slot, a tile of E
a grid step. 0 when no such kernel or no round is in the window."""
from perfbench import manifest

read = manifest.load_module("metrics", "ssm_update_ms_round.chat").read
