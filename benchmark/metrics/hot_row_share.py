"""Share of the rows the signer-table partition sent to the hot
radix-256 kernel: meter ``crypto.verify.signer_table.hot_rows`` over
hot + cold rows in the window."""


def read(ctx):
    hot = ctx["accounting"]["hot_rows"]
    cold = ctx["accounting"]["cold_rows"]
    return hot / (hot + cold) if hot + cold else None
