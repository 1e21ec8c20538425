"""One module per way of driving the system: `serve` (open and closed loops
over HTTP against a deployed replica) and `train` (a JaxTrainer job). A
traffic file names its runner."""
