"""One module per deployment family: operands from the seed, each
request's payload, the plain reference and the work of one request."""
