"""Proxy and store: host time the served path spends modelling the network
(``memec.netsim``: every ``NetSim`` phase and record) in the traced
sub-window, per op the traced window's calls carried."""
from bench import spans


def read(run):
    return spans.self_ms_per_op(run, "memec.netsim")
