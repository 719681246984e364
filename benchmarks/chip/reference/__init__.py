"""Plain references the benchmark compares the program with.

They import nothing of the program.  ``common`` holds what every family
shares (weights drawn from the seed, AdamW, a gradient blocked over
sequences, per-leaf norms); each model family is a module of its own that
defines ``init(draw, model)`` and ``loss(params, tokens, model)``.
"""
