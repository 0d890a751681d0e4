"""A fixed learning rate: ``initial-rate:<r>`` at every step."""


def rate(step, args):
    del step
    rates = [arg.split(":", 1)[1] for arg in args if arg.startswith("initial-rate:")]
    if len(rates) != 1 or len(args) != 1:
        raise SystemExit("a fixed rate takes initial-rate:<r> alone; configuration gives %r"
                         % (args,))
    return float(rates[0])
