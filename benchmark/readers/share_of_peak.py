"""What the ALGORITHM needs over what the chip could do in the window:
100 * record[amount] / (window x chips x peak), where the amount (FLOPs or
bytes) was counted by the runner from the cell's shapes and its own record
of lengths (benchmark/flops.py) — never from what the program dispatches —
and the peak is a row of benchmark/peaks.json. Nothing counted, or no
published peak for the device, is nothing to read: never 0."""


def read(record, amount: str, peak: str):
    if not record.get("peaks") or not record.get(amount):
        return None
    return 100.0 * record[amount] / (
        record["window_s"] * record["chips"] * record["peaks"][peak])
