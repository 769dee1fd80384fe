"""Plain float32 references, one module per model family, found by name.

``bench/reference/<name>.py`` (a configuration's ``reference``) has
``logits(params, tokens, m, positions)``: a straightforward forward pass
over one sequence at float32 and the highest matmul precision, computed a
layer at a time so it fits beside the served weights, returning the logits
at ``positions`` over the true vocabulary. They import nothing of the
program under test; they read the weights the benchmark made. A module is
loaded from its path under the benchmark root, so a copied root can add one
as a file.
"""
from pathlib import Path

from bench import found

HERE = Path(__file__).resolve().parent


def module(name: str, base: Path = HERE):
    return found.module(base, name, "reference")
