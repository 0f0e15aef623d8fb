"""Compile requests inside the window that the persistent cache did not
serve: programs compiled while jobs were being timed.  Expected 0."""


def read(run, args):
    w = run.compiles["window"]
    return w["requests"] - w["hits"]
