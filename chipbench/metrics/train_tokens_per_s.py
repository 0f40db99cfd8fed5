"""Every token trained in the window over the window's time (host clock;
each step's loss is read before the next starts)."""


def read(run):
    tokens = run.units.get("tokens")
    if not tokens:
        return None
    return tokens / run.window_s
