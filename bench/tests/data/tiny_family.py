"""A test-only architecture family: the dense decoder's reference, scales
and counts, with three marks a test can see: ``wq`` made all ones, every
decoded token counted at 1e12 FLOPs, and each call of ``logits`` logged."""
from bench.counts import *  # noqa: F401,F403  (the dense counts)
from bench import reference

CALLS = []


def scale_for(names, shape, sizes):
    return ("ones", 0.0) if names[-1] == "wq" else None


def decode_flops(c, context):
    return 1e12


def logits(cfg, rest, layer, seqs, want, precision="f32"):
    CALLS.append(precision)
    return reference.logits(cfg, rest, layer, seqs, want, precision)
