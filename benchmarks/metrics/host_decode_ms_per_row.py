"""Worker-side ``decode`` stage seconds over the window, from the program's stage
registry (the difference of two snapshots), per row delivered in the window."""


def read(run):
    if not run['decode_s'] or not run['rows_in_window']:
        return None
    return 1e3 * run['decode_s'] / run['rows_in_window']
