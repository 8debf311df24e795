"""Open loop: Poisson arrivals at the mix's fixed ``rate_rps``
(serving.open_loop); ``sweep`` steps the rate to find the knee."""
from chipbench import serving


def run(c, args, clock, counter):
    return serving.run(c, args, clock, "open", counter)


def sweep(c, args, rates, counter):
    return serving.sweep(c, args, rates, counter)


def readings(c, seeds, control_seeds, seconds, counter):
    return serving.readings(c, seeds, control_seeds, seconds, counter)
