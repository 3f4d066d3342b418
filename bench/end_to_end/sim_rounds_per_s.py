"""Simulated rounds completed per second: all the work of the window over
all its time, on the host clock."""


def value(window: dict) -> float:
    work = sum(w for _, _, w in window["calls"])
    return work / (window["end"] - window["start"])
