"""Open loop: each request is due at its scheduled offset from the window's
start, whatever the engine is doing, and is submitted at the first tick
boundary after it (the engine reads its queue only there)."""


def run(driver, schedule, t0: float, end: float):
    planned = schedule.requests
    i = 0
    while True:
        now = driver.clock()
        if now >= end:
            return
        while i < len(planned) and t0 + planned[i].due_s <= now:
            driver.submit(planned[i], t0 + planned[i].due_s)
            i += 1
        if driver.busy():
            driver.tick()
        else:
            driver.wait_until(min(t0 + planned[i].due_s if i < len(planned) else end, end))
