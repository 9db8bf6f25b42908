"""Mean events per batch published in the window (``on_publish``)."""


def read(record):
    if not record.publishes:
        return None
    return sum(p["events"] for p in record.publishes) / len(record.publishes)
