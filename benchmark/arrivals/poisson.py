"""Independent users: exponential gaps at ``rate`` requests a second."""


def gaps(params: dict, rate: float, rng):
    while True:
        yield rng.expovariate(rate)
