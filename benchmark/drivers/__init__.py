"""One module per way of driving the system: `run(ctx) -> result`."""
