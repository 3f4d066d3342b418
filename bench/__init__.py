"""The chip benchmark: harness, drivers, plain references and trace reduction."""
