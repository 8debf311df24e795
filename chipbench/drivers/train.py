"""Training: warm-started ``train_gan`` calls of the mix's
``epochs_per_call`` epochs over ``rows`` rows (training.py)."""
from chipbench import training


def run(c, args, clock, counter):
    return training.run(c, args, clock, counter)


def readings(c, seeds, control_seeds, seconds, counter):
    return training.readings(c, seeds, control_seeds, seconds, counter)
