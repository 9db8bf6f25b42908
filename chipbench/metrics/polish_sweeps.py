"""Float64 sweeps of the DF-P polish: the mean of the ``sweeps`` argument
over the window's ``polish.f64`` spans."""


def read(record):
    sweeps = [s.args["sweeps"] for s in record.window_spans("polish.f64")
              if "sweeps" in s.args]
    if not sweeps:
        return None
    return sum(sweeps) / len(sweeps)
