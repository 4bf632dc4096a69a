"""One module per entry kind; ``harness`` imports it by the name a cell's
workload file gives."""
