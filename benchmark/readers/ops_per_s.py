"""Correct-so-far answers completed per second: requests whose reply
was read inside the window with HTTP 200, over the window's length. A
reply found wrong afterwards sets `correct` false and counts in
`failed`; it is the run, not the rate, that is then refused."""


def read(ctx):
    return ctx["completed"] / ctx["seconds"]
