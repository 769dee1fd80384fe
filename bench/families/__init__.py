"""One module per model family, found by the family's name: the model dict
of a configuration (``bench/configs``) names it as ``family``, and
``bench/families/<family>.py`` holds all that the benchmark knows of its
layers. A new architecture is a new file here, beside its reference in
``bench/reference``; ``weights``, ``costs`` and ``harness`` keep what every
family shares and never name one.

A family module defines:

  PROGRAM_FAMILY  the program's ``ArchConfig.family`` that it serves as
  blocks(m)       the leaves of the weight tree's ``blocks``, each
                  (shape, dtype, init, std) as ``bench/weights.py`` draws
                  them, stacked over ``m["num_layers"]``
  INITS           {init: draw(key, shape, dtype)} for initialisations of
                  its own (``weights`` draws "normal", "ones" and "zeros")
  layer_params(m) the matrix parameters of one layer (one multiply-add per
                  token each)
  decode(m, positions), chunk(m, rows, pos, tokens)
                  (FLOPs, bytes) that a decode step over slots at
                  ``positions``, or a prefill chunk, needs beyond the
                  matrices: attention over the context, or the recurrent
                  state (``bench/costs.py`` adds the matrices)
  program_fields(m)
                  the ``ArchConfig`` fields that ``m`` sets beyond the
                  shared ones (``harness.program_config``)
"""
from pathlib import Path

from bench import found

HERE = Path(__file__).resolve().parent


def module(family: str, base: Path = HERE):
    return found.module(base, family, "model family")
