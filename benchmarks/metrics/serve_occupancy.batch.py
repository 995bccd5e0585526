"""serving scheduler: mean number of sequences in a decode step (the
``batch`` argument of each ``serve/decode`` span) over the slots."""


def read(run):
    sizes = [args.get("batch") for name, _, _, args in run["records"].get("span_args", ()) if name == "serve/decode"]
    sizes = [s for s in sizes if s is not None]
    if not sizes or not run["records"].get("slots"):
        return None
    return 100.0 * sum(sizes) / len(sizes) / run["records"]["slots"]
