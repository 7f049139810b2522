"""Rounds in the measured window (a reader that only this fixture has)."""


def read(ctx):
    return ctx["n_rounds"]
