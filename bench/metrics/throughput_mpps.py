"""Packets whose egress was ready on the device before the window closed,
over the window's length, in millions per second (host clock)."""


def read(run):
    return sum(r.packets for r in run.completed) / run.seconds / 1e6
