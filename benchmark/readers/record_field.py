"""A number the runner measured itself (set-up seconds)."""


def read(record, field: str):
    return record.get(field)
