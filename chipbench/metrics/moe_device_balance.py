"""Mean over the window's steps of the step's own ``balance`` counter: the
max over the mean device load after scheduling.  One device is always
balanced, so a one-chip cell has nothing to read."""


def reduce(run):
    if run.chips < 2 or not run.balance:
        return None
    return sum(run.balance) / len(run.balance)
