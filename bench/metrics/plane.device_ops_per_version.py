"""Operators per version whose work ran in a jitted kernel on the device
(``ExecStats.ops_on_device``)."""


def read(run):
    ans = run.window_answers
    return sum(a.ops_on_device for a in ans) / len(ans) if ans else None
