"""Plain float32 references, one module per model family.

Each module has ``logits(params, tokens, m, positions)``: a straightforward
forward pass over one sequence at float32 and the highest matmul precision,
computed a layer at a time so it fits beside the served weights, returning
the logits at ``positions`` over the true vocabulary. They import nothing of
the program under test; they read the weights the benchmark made.
"""
import importlib


def module(family: str):
    return importlib.import_module(f"{__name__}.{family}")
