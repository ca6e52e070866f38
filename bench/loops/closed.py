"""Closed loop: ``schedule.clients`` callers, each sending its next request
(the schedule's requests in order, starting over at the end) when its
previous one has finished, after ``schedule.think_s`` seconds."""


def run(driver, schedule, t0: float, end: float):
    planned = schedule.requests
    due = [t0] * schedule.clients           # when each caller sends next
    i = 0
    while True:
        now = driver.clock()
        if now >= end:
            return
        for c in range(len(due)):
            if due[c] is not None and due[c] <= now:
                rec = driver.submit(planned[i % len(planned)], due[c])
                i += 1
                rec.client = c
                due[c] = None if not rec.failed else now
        if driver.busy():
            for rec in driver.tick():
                due[rec.client] = rec.finish + schedule.think_s
        else:
            pending = [d for d in due if d is not None]
            driver.wait_until(min(min(pending, default=end), end))
