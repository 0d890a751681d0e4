"""Plain SGD: theta <- theta - rate * aggregated gradient.  No state."""


def init(theta, args):
    if args:
        raise SystemExit("plain sgd takes no optimizer arguments; configuration gives %r" % (args,))
    return None


def step(theta, aggregated, state, rate):
    return theta - rate * aggregated, state
