"""WideJAX: MPWide's wide-area communication model reproduced on JAX."""
