"""Plain references and the inputs the benchmark serves, copied so that
no change to the program can move them."""
