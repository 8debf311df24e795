"""Closed loop: each of the mix's ``clients`` sends its next request when
its last one is answered (serving.closed_loop)."""
from chipbench import serving


def run(c, args, clock, counter):
    return serving.run(c, args, clock, "closed", counter)


def readings(c, seeds, control_seeds, seconds, counter):
    return serving.readings(c, seeds, control_seeds, seconds, counter)
