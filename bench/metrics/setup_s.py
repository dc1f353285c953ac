"""Seconds from process start to the opening of the measured window:
imports, weights, compilation or cache loads, and warm-up."""


def read(rec):
    return rec["setup_s"]
