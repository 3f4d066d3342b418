"""Plain references of what the timed paths compute; nothing of the program."""
