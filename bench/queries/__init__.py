"""One module per TPC-H query: its reference, bindings and required bytes."""
