"""Work completed in the window over the WHOLE window: `record[count]` /
window_s, per chip where asked. All the work, all the time."""


def read(record, count: str, per_chip: bool = False):
    if not record.get(count) or not record.get("window_s"):
        return None
    rate = record[count] / record["window_s"]
    return rate / record["chips"] if per_chip else rate
