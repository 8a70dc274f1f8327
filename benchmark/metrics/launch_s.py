"""Control plane (client/cli.py -> cluster/coordinator.py ->
cluster/executor.py): host clock from the benchmark command's start to the
first line of the job script, which stamps it."""


def read(ctx):
    return ctx["counters"].get("launch_s")
