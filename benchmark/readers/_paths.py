"""Helpers the counter readers share: walk a snapshot by a list path."""


def dig(doc, path):
    for key in path:
        if not isinstance(doc, dict) or key not in doc:
            return None
        doc = doc[key]
    return doc


def delta(ctx, path):
    """after - before of one cumulative counter; None where the program
    does not publish it."""
    a, b = dig(ctx["after"], path), dig(ctx["before"], path)
    if a is None:
        return None
    return a - (b or 0)
