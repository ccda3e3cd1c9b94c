"""Process start (the first line of run.py) to the first measured instant."""


def read(evidence, args):
    return evidence["window"][0] - args["cell"].t_process_start
