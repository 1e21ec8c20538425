"""One module per kind of reader. A reader is `read(data, args)`: `data` is
what the runner gathered (see runners/), `args` the metric file's own
arguments. It returns a number, or None when there is nothing to read — the
harness then leaves the metric out of the line."""
