"""Tokens the engine returned inside the window, over the window's
seconds (host clock)."""


def read(run):
    s = run["seconds"]
    return sum(1 for r in run["requests"] for t in r["tokens"]
               if 0 <= t <= s) / s
