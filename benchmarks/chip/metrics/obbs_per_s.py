"""Link OBBs given a verdict over the whole window, divided by the window:
every call of a closed loop, from the first call's start to the last
call's end."""


def read(run):
    if not run.calls:
        return None
    return len(run.calls) * run.unit_obbs / run.window_s
