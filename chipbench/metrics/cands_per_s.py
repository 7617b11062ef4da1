"""Candidate placements answered inside the window, per second of the window."""


def read(ctx):
    return ctx.candidates_answered / ctx.seconds
