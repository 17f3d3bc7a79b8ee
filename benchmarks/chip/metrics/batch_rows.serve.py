"""Mean query rows per batch the server ran in the window, from the
server's batch-size histogram."""


def read(record):
    if not record.get("batches"):
        return None
    return record["batch_rows"] / record["batches"]
