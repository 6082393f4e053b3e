"""Phi-3 (model_type "phi3") is served and checked as llama.py's dense GQA
decoder: its fused qkv and gate-up projections hold the same matrices,
and contexts under its sliding window make attention full and causal."""
from families.llama import *  # noqa: F401,F403
